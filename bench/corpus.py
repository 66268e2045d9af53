"""The served corpus and question pool, generated from a mix's seed.

A copy of the synthetic SQuAD 2.0 generator of the system under test
(``repro.data.synthetic_squad``), kept here so that later changes to
the program cannot change the benchmark's inputs.  Two departures,
both parameters of the mix file:

* paragraph length has SQuAD's long tail: the number of facts per
  paragraph and the filler words per fact are drawn per paragraph, so
  most paragraphs hold about 120 words and a few several hundred;
* the corpus and the question pool are sized by the mix.

Paragraphs state facts "the <attr> of <subject> is <value>"; answerable
questions ask for a fact that some paragraph holds, unanswerable ones
for an attribute that no paragraph gives its subject.  The same seed
gives the same corpus and questions.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

TOPICS = ["river", "empire", "composer", "protocol", "mineral", "galaxy",
          "treaty", "enzyme", "cathedral", "glacier", "dynasty", "reactor",
          "archipelago", "manuscript", "observatory", "aqueduct"]
ATTRS = ["length", "origin", "founder", "capital", "color", "height",
         "population", "discoverer", "age", "temperature", "successor",
         "architect", "purpose", "location", "composition", "name"]
FILLER = ("historians note that records describe how scholars later "
          "established that during the period many sources agree the "
          "region was widely known for its significance").split()


@dataclass
class Question:
    """Field for field what the system's ``Question`` carries."""
    qid: int
    text: str
    answerable: bool
    gold_answer: Optional[str]
    gold_pid: Optional[int]


@dataclass
class Corpus:
    texts: List[str]
    questions: List[Question]


def generate(spec: Dict, seed: int) -> Corpus:
    """``spec`` is the ``corpus`` object of a mix file."""
    rng = np.random.default_rng(seed)
    n_par = int(spec["n_paragraphs"])
    reuse = float(spec.get("subject_reuse", 4.0))
    attr_alias = float(spec.get("attr_alias_prob", 0.3))
    subj_alias = float(spec.get("subject_alias_prob", 0.1))
    facts_mu, facts_sigma = spec["facts_lognormal"]
    fill_mu, fill_sigma = spec["filler_lognormal"]
    fill_max = int(spec["filler_max"])

    facts: Dict[str, Dict[str, str]] = {}
    fact_loc: Dict[str, int] = {}
    pool_size = max(1, int(n_par / reuse))
    pool = [f"{TOPICS[rng.integers(0, len(TOPICS))]}{i:04d}"
            for i in range(pool_size)]
    texts = []
    for pid in range(n_par):
        subject = pool[rng.integers(0, pool_size)]
        shown_subj = (f"{subject}x" if rng.random() < subj_alias
                      else subject)
        n_facts = int(np.clip(round(rng.lognormal(facts_mu, facts_sigma)),
                              1, len(ATTRS)))
        facts.setdefault(subject, {})
        sents = []
        for ai in rng.choice(len(ATTRS), size=n_facts, replace=False):
            attr = ATTRS[ai]
            val = f"val{rng.integers(0, 99999):05d}"
            if attr not in facts[subject]:
                facts[subject][attr] = val
                fact_loc[f"{subject}|{attr}"] = pid
            shown = f"{attr}form" if rng.random() < attr_alias else attr
            n_fill = int(np.clip(round(rng.lognormal(fill_mu, fill_sigma)),
                                 1, fill_max))
            filler = " ".join(rng.choice(FILLER, size=n_fill))
            sents.append(f"the {shown} of {shown_subj} is {val} . {filler} .")
        rng.shuffle(sents)
        texts.append(" ".join(sents))

    subjects = list(facts)
    n_q = int(spec["n_questions"])
    n_ans = int(n_q * float(spec.get("answerable_frac", 0.5)))
    questions = []
    for qid in range(n_q):
        if qid < n_ans:
            subj = subjects[rng.integers(0, len(subjects))]
            attrs = list(facts[subj])
            attr = attrs[rng.integers(0, len(attrs))]
            questions.append(Question(
                qid, f"what is the {attr} of {subj} ?", True,
                facts[subj][attr], fact_loc[f"{subj}|{attr}"]))
        else:
            while True:
                subj = subjects[rng.integers(0, len(subjects))]
                missing = [a for a in ATTRS if a not in facts[subj]]
                if missing:
                    break
            attr = missing[rng.integers(0, len(missing))]
            questions.append(Question(
                qid, f"what is the {attr} of {subj} ?", False, None, None))
    rng.shuffle(questions)
    for i, q in enumerate(questions):
        q.qid = i
    return Corpus(texts, questions)
