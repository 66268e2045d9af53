"""Host scheduler unit tests over a pure numpy fake executor.

The scheduler/executor split means admission grouping, slot reuse, and
harvest correctness are testable without any JAX compute: the fake
implements the DeviceExecutor protocol (admit / decode_chunk /
sync_control / fetch_outputs) with a scripted greedy "model"."""
import numpy as np
import pytest

from repro.data.tokenizer import EOS, PAD
from repro.serving.continuous import ContinuousEngine


class FakeExecutor:
    """Scripted executor: ``gen_fn(prompt) -> full greedy token list``
    (first element is the prefill output).  Mirrors the device
    semantics exactly: out[0]/gen=1/active at admit, ``sync_every``
    steps per decode chunk, stop on EOS or the per-request limit."""

    def __init__(self, gen_fn, *, num_slots=4, max_len=64, max_new_cap=16,
                 sync_every=2, prefill_batch=1):
        self.gen_fn = gen_fn
        self.num_slots = num_slots
        self.max_len = max_len
        self.max_new_cap = max_new_cap
        self.sync_every = sync_every
        self.prefill_batch = max(1, min(prefill_batch, num_slots))
        self.cache_allocations = 0
        S, cap = num_slots, max_new_cap
        self._seq = [None] * S          # scripted continuation per slot
        self._limit = np.zeros(S, np.int32)
        self._active = np.zeros(S, bool)
        self._gen = np.zeros(S, np.int32)
        self._out = np.zeros((S, cap), np.int32)
        self.admit_log = []             # [(plen, [prompts])] per dispatch

    def admit(self, tokens, slot_idx, limits):
        group = []
        for row, slot, lim in zip(tokens, slot_idx, limits):
            if slot >= self.num_slots:
                continue                 # unused scratch row
            prompt = list(row)
            while prompt and prompt[-1] == PAD:
                prompt.pop()
            group.append(prompt)
            seq = list(self.gen_fn(prompt))
            assert len(seq) >= self.max_new_cap
            self._seq[slot] = seq
            self._limit[slot] = lim
            self._out[slot, 0] = seq[0]
            self._gen[slot] = 1
            self._active[slot] = (seq[0] != EOS) and (lim > 1)
        self.admit_log.append((tokens.shape[1], group))

    def decode_chunk(self):
        for _ in range(self.sync_every):
            for s in range(self.num_slots):
                if not self._active[s]:
                    continue
                tok = self._seq[s][self._gen[s]]
                self._out[s, self._gen[s]] = tok
                self._gen[s] += 1
                self._active[s] = (tok != EOS) and \
                    (self._gen[s] < self._limit[s])

    def sync_control(self):
        return self._active.copy(), self._gen.copy()

    def fetch_outputs(self):
        return self._out.copy()


def expected(seq, limit):
    """What the engine should emit: seq truncated at EOS (inclusive),
    capped at limit."""
    out = []
    for t in seq[:limit]:
        out.append(t)
        if t == EOS:
            break
    return out


def arith_gen(prompt):
    """Deterministic non-EOS continuation derived from the prompt."""
    base = sum(prompt) % 40
    return [4 + (base + i) % 40 for i in range(64)]


def make_engine(gen_fn=arith_gen, **kw):
    eng_kw = {k: kw.pop(k) for k in ("admission_lookahead",
                                     "prefill_pad_multiple") if k in kw}
    fake = FakeExecutor(gen_fn, **kw)
    return ContinuousEngine(executor=fake, **eng_kw), fake


def _prompts(lens, seed=0):
    rng = np.random.default_rng(seed)
    return [list(rng.integers(4, 60, size=n)) for n in lens]


def test_requires_model_or_executor():
    with pytest.raises(ValueError):
        ContinuousEngine()


def test_scripted_generation_and_slot_reuse():
    """More requests than slots: every request completes with exactly
    its scripted tokens, slots are reused, concurrency is bounded."""

    def gen(prompt):
        # EOS position scripted by prompt length
        n = len(prompt)
        return arith_gen(prompt)[:n] + [EOS] + [7] * 64

    eng, fake = make_engine(gen, num_slots=2, sync_every=2)
    prompts = _prompts([3, 6, 2, 9, 4])
    outs = eng.generate_many(prompts, max_new_tokens=8)
    assert len(outs) == 5
    for p, o in zip(prompts, outs):
        want = expected(gen(p), 8)
        assert list(o.tokens) == want, (p, want, list(o.tokens))
        assert o.n_steps == len(want)
    assert eng.stats.n_completed == 5
    assert eng.stats.n_admitted == 5
    assert eng.stats.max_concurrent == 2      # bounded by the slot pool
    assert eng.stats.cache_allocations == 0   # fake allocates nothing


def test_fifo_admission_order():
    """With a single slot, requests are admitted strictly in submission
    order (no reordering across waves of slot reuse)."""
    eng, fake = make_engine(num_slots=1, sync_every=2)
    prompts = _prompts([4, 5, 6, 7])
    eng.generate_many(prompts, max_new_tokens=4)
    admitted = [g[0] for _, g in fake.admit_log]
    assert admitted == prompts


def test_immediate_finish_limit_one_no_decode():
    """max_new_tokens=1 requests finish at prefill and never enter the
    decode loop."""
    eng, fake = make_engine(num_slots=2)
    outs = eng.generate_many(_prompts([3, 3, 3]), max_new_tokens=1)
    assert [o.n_steps for o in outs] == [1, 1, 1]
    assert eng.stats.n_decode_chunks == 0


def test_eos_as_first_token_finishes_at_prefill():
    eng, fake = make_engine(lambda p: [EOS] + [9] * 64, num_slots=2)
    outs = eng.generate_many(_prompts([3, 4]), max_new_tokens=8)
    assert [list(o.tokens) for o in outs] == [[EOS], [EOS]]
    assert eng.stats.n_decode_chunks == 0


def test_lookahead_grouping_fixes_head_of_line_blocking():
    """One odd-length prompt at the head must not degrade batched
    prefill to singletons: the lookahead window regroups equal-padded-
    length prompts ([5,9,9,5,5] with batch 3 -> [5,5,5] + [9,9]),
    while a 1-deep window reproduces the old consecutive-only grouping
    ([5] + [9,9] + [5,5]).  Outputs are identical either way."""
    lens = [5, 9, 9, 5, 5]

    eng, fake = make_engine(num_slots=8, prefill_batch=3)
    outs = eng.generate_many(_prompts(lens), max_new_tokens=6)
    assert eng.stats.n_prefills == 2
    assert sorted(len(g) for _, g in fake.admit_log) == [2, 3]

    eng1, fake1 = make_engine(num_slots=8, prefill_batch=3,
                              admission_lookahead=1)
    outs1 = eng1.generate_many(_prompts(lens), max_new_tokens=6)
    assert eng1.stats.n_prefills == 3
    assert [len(g) for _, g in fake1.admit_log] == [1, 2, 2]
    assert [list(o.tokens) for o in outs] == [list(o.tokens) for o in outs1]


def test_lookahead_skipped_prompts_keep_queue_order():
    """Prompts skipped by the lookahead window are admitted later in
    their original relative order."""
    lens = [5, 9, 5, 9, 9]
    eng, fake = make_engine(num_slots=2, prefill_batch=2)
    prompts = _prompts(lens)
    eng.generate_many(prompts, max_new_tokens=4)
    flat = [p for _, g in fake.admit_log for p in g]
    # first group pairs the two len-5 prompts; the len-9s follow FIFO
    assert flat[0] == prompts[0] and flat[1] == prompts[2]
    assert flat[2:] == [prompts[1], prompts[3], prompts[4]]


def test_pad_multiple_groups_by_padded_length():
    """prefill_pad_multiple buckets raw lengths: 5 and 7 both pad to 8,
    so they prefill as one group."""
    eng, fake = make_engine(num_slots=4, prefill_batch=4,
                            prefill_pad_multiple=8)
    eng.generate_many(_prompts([5, 7, 5]), max_new_tokens=4)
    assert eng.stats.n_prefills == 1
    assert fake.admit_log[0][0] == 8  # padded length


def test_interleaved_runs_keep_results_separate():
    eng, fake = make_engine(num_slots=2)
    a = eng.generate_many(_prompts([3, 4], seed=1), max_new_tokens=4)
    b = eng.generate_many(_prompts([5, 6], seed=2), max_new_tokens=4)
    assert {o.rid for o in a}.isdisjoint({o.rid for o in b})


# --- per-request reject path -------------------------------------------------


def test_strict_submit_still_raises_on_overflow():
    eng, fake = make_engine(num_slots=2, max_len=16, max_new_cap=8)
    with pytest.raises(ValueError):
        eng.submit(0, list(range(4, 18)), max_new_tokens=8)
    with pytest.raises(ValueError):
        eng.submit(1, [], max_new_tokens=2)


def test_nonstrict_overlength_rejected_per_request_stream_alive():
    """One over-length prompt in a mixed stream is rejected as a failed
    CompletedGeneration; every other request still completes with its
    exact scripted tokens and the rejected one is never admitted."""
    eng, fake = make_engine(num_slots=2, max_len=16, max_new_cap=8)
    good = _prompts([4, 5, 6])
    long_prompt = list(range(4, 4 + 14))        # 14 + 8 > max_len 16
    rids = [eng.reserve_rid() for _ in range(4)]
    assert eng.submit(rids[0], good[0], 6) is True
    assert eng.submit(rids[1], long_prompt, 6, strict=False) is False
    assert eng.submit(rids[2], good[1], 6) is True
    assert eng.submit(rids[3], good[2], 6) is True
    done = eng.run()
    assert set(done) == set(rids)
    rej = done[rids[1]]
    assert rej.failed and "max_len" in rej.failed
    assert rej.n_steps == 0 and len(rej.tokens) == 0
    for rid, p in zip((rids[0], rids[2], rids[3]), good):
        assert list(done[rid].tokens) == expected(arith_gen(p), 6)
    # the rejected prompt never reached the executor
    admitted = [p for _, g in fake.admit_log for p in g]
    assert long_prompt not in admitted
    assert eng.stats.n_rejected == 1
    assert eng.stats.n_admitted == 3 and eng.stats.n_completed == 3


def test_nonstrict_empty_prompt_rejected():
    eng, fake = make_engine(num_slots=2)
    rid = eng.reserve_rid()
    assert eng.submit(rid, [], 4, strict=False) is False
    done = eng.run()
    assert done[rid].failed == "empty prompt"
    assert eng.stats.n_rejected == 1 and not fake.admit_log


def test_nonstrict_reject_with_slots_resident_mid_flight():
    """The Gateway failure mode: requests already resident in slots
    must survive a mid-flight rejection (submit while a wave is being
    drained) — scripted via two submit waves into one run()."""
    eng, fake = make_engine(num_slots=1, max_len=16, max_new_cap=8)
    p0, p1 = _prompts([4, 5])
    r0, r1, r2 = (eng.reserve_rid() for _ in range(3))
    eng.submit(r0, p0, 6)
    eng.submit(r1, list(range(4, 4 + 15)), 6, strict=False)  # rejected
    eng.submit(r2, p1, 6)
    done = eng.run()
    assert done[r1].failed
    assert list(done[r0].tokens) == expected(arith_gen(p0), 6)
    assert list(done[r2].tokens) == expected(arith_gen(p1), 6)


def test_first_token_stamp_is_first_sync_showing_a_token():
    """``first_token_at`` is the engine clock when the first control
    sync after a request's admission returned (the first sync that
    shows ``gen >= 1`` for its slot), not the prefill dispatch; and
    ``admitted_at <= first_token_at <= finished_at``."""
    eng, fake = make_engine(num_slots=2, sync_every=2, prefill_batch=1)
    t = [0.0]
    eng._clock = lambda: t[0]
    admit, sync = fake.admit, fake.sync_control
    admits, syncs = [], []       # (t, slots); (t after the sync, gen)

    def rec_admit(tokens, slot_idx, limits):
        t[0] += 0.25
        admits.append((t[0], [int(s) for s in slot_idx]))
        return admit(tokens, slot_idx, limits)

    def rec_sync():
        t[0] += 1.0              # the sync blocks on the device
        out = sync()
        syncs.append((t[0], out[1].copy()))
        return out

    fake.admit, fake.sync_control = rec_admit, rec_sync
    prompts = _prompts([3, 6, 2, 9, 4])
    outs = eng.generate_many(prompts, max_new_tokens=6)
    assert len(admits) == 5
    for (t_adm, slots), o in zip(admits, outs):
        slot = slots[0]
        want = next(ts for ts, gen in syncs
                    if ts > t_adm and gen[slot] >= 1)
        assert o.first_token_at == want
        assert o.admitted_at < o.first_token_at <= o.finished_at
    # a request that decodes past its first chunk finishes later still
    assert any(o.first_token_at < o.finished_at for o in outs)
    assert not eng._first_token_at          # no stamp outlives its slot
