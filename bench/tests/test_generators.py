"""Traffic and corpus generators are fixed by their seeds, and every
seed gets the same amount of work."""
import json
from pathlib import Path

import numpy as np

import corpus
import traffic

MIXES = Path(__file__).resolve().parents[1] / "mixes"


def small_corpus_spec():
    spec = json.loads((MIXES / "rag_steady.json").read_text())["corpus"]
    return dict(spec, n_paragraphs=200, n_questions=100)


def test_corpus_is_fixed_by_its_seed():
    a = corpus.generate(small_corpus_spec(), 3)
    b = corpus.generate(small_corpus_spec(), 3)
    c = corpus.generate(small_corpus_spec(), 4)
    assert a.texts == b.texts and a.questions == b.questions
    assert a.texts != c.texts
    words = [len(t.split()) for t in a.texts]
    assert 90 < np.mean(words) < 150 and max(words) > 2 * np.median(words)
    gold = [q for q in a.questions if q.answerable]
    assert all(q.gold_answer in a.texts[q.gold_pid] for q in gold)


def test_schedule_is_fixed_by_its_seed():
    arr = json.loads((MIXES / "rag_steady.json").read_text())["arrivals"]
    one = traffic.open_loop_schedule(arr, 30.0, np.random.default_rng(7))
    two = traffic.open_loop_schedule(arr, 30.0, np.random.default_rng(7))
    other = traffic.open_loop_schedule(arr, 30.0, np.random.default_rng(8))
    assert one == two and one != other
    n = round(traffic.mean_rate(arr) * 30.0)
    assert len(one) == len(other) == n
    assert all(0 <= t < 30.0 for t in one) and one == sorted(one)
    # every seed: n of the same n + 1 Poisson gaps, in another order
    ga = np.round(np.diff([0.0] + one), 9)
    gb = np.round(np.diff([0.0] + other), 9)
    assert len(np.intersect1d(ga, gb)) >= n - 1


def test_bursty_schedule_keeps_its_count():
    arr = {"process": "onoff", "burst_rate": 20.0, "on_s": 0.5,
           "off_s": 1.5}
    a = traffic.open_loop_schedule(arr, 20.0, np.random.default_rng(1))
    b = traffic.open_loop_schedule(arr, 20.0, np.random.default_rng(2))
    assert len(a) == len(b) == 100 and a != b
