"""Device executors for the continuous engine.

The host scheduler in :mod:`repro.serving.continuous` is device-agnostic:
it plans admissions, tracks slot ownership, and harvests finished
requests — all in numpy.  Everything that touches device buffers lives
behind the :class:`DeviceExecutor` protocol implemented here:

* :class:`SingleDeviceExecutor` — the original single-device path: slot
  cache + prefill scratch allocated once, jitted prefill / fused
  insert+state-commit / K-step decode chunk, donated buffers.
* :class:`ShardedExecutor` — the same jitted programs laid out over a
  ``dp×mp`` ``jax.sharding.Mesh``.  The SLOT dimension partitions on
  the data axis(es): KV cache, slot control arrays, and output buffer
  are all ``NamedSharding``-placed and the jits carry matching
  ``out_shardings``, so each device owns ``num_slots / dp`` slot rows
  end-to-end — decode never moves a slot row across devices.  Params
  place via :func:`repro.sharding.shardings_for_schema` over the model
  schema's logical axes (``fsdp=False`` — inference wants weights
  resident, not ZeRO-gathered), so on an ``mp>1`` mesh attention heads
  / FFN / vocab dims shard over the ``model`` axis and every jitted
  program runs tensor-parallel; KV-cache ``kv_heads`` dims ride the
  same axis, keeping each model shard's cache writes local.  The
  prefill scratch shards its rows over ``data`` when ``prefill_batch``
  divides the data-axis size (large admission groups no longer
  replicate prefill work; the insert scatter all-gathers the few
  scratch rows), and falls back to replicated rows otherwise.  On a
  ``mp=1`` mesh every param spec degenerates to replicated — the
  original slot-data-parallel layout.

Both executors dispatch asynchronously (JAX async dispatch): ``admit``
and ``decode_chunk`` return as soon as the work is enqueued, and the
host only blocks in ``sync_control`` / ``fetch_outputs``.  That is what
lets the scheduler overlap the next admission group's prefill with the
decode chunk already in flight.

Protocol (duck-typed; see ``tests/test_host_scheduler.py`` for a pure
numpy fake):

    admit(tokens (PB, plen) i32, slot_idx (PB,) i32, limits (PB,) i32)
        prefill the padded prompt rows, scatter them into their slots,
        and commit first-token / active / limit state.  Rows whose
        ``slot_idx == num_slots`` are unused scratch rows and dropped.
    decode_chunk()
        advance every slot ``sync_every`` greedy steps (async).
    sync_control() -> (active (S,) bool, gen (S,) i32)
        block and download the two tiny control arrays.
    fetch_outputs() -> (S, max_new_cap) i32
        block and download the output buffer.
    attrs: num_slots, max_len, max_new_cap, sync_every, prefill_batch,
        cache_allocations.

    Optional health extensions (the scheduler probes via ``getattr`` so
    pure-numpy fakes without them keep working):

    slot_faults() -> (S,) bool
        per-slot poison flags: a slot goes bad when any of its decode
        logits turn NaN/inf (detected on-device inside the chunk scan —
        the slot is immediately deactivated there so it stops writing
        tokens, and stays flagged until cleared).
    deactivate(slots)
        clear the active bits for the given slots (quarantine/cancel).
    clear_slot_faults(slots)
        reset poison flags (scheduler quarantine reset).

    Health checks are on by default; ``health_checks=False`` removes
    the isfinite test from the decode scan entirely.
"""
from __future__ import annotations

import contextlib
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.data.tokenizer import EOS, PAD
from repro.sharding import (batch_axes, input_sharding, mesh_axis_sizes,
                            shardings_for_schema)


class SingleDeviceExecutor:
    """Slot cache + jitted prefill/commit/decode on the default device."""

    def __init__(self, model, params, *, num_slots: int = 8,
                 max_len: int = 512, max_new_cap: int = 64,
                 sync_every: int = 4, prefill_batch: int = 1,
                 moe_fn: Optional[Callable] = None,
                 mla_absorb: bool = False, health_checks: bool = True,
                 paged: bool = False, page_size: int = 16,
                 num_pages: Optional[int] = None, metrics=None):
        self.model = model
        self.params = params
        self.num_slots = num_slots
        self.max_len = max_len
        self.max_new_cap = max_new_cap
        self.sync_every = sync_every
        self.prefill_batch = max(1, min(prefill_batch, num_slots))
        self.moe_fn = moe_fn
        self.mla_absorb = mla_absorb
        self.health_checks = health_checks
        self.paged = paged
        self.page_partitions = 1

        # the ONLY cache allocations in the executor's lifetime: the
        # slot cache (dense per-slot rows, or the global page pool +
        # block tables) and the dense prefill scratch (reused forever)
        if paged:
            if max_len % page_size != 0:
                raise ValueError(f"max_len={max_len} must be a multiple "
                                 f"of page_size={page_size}")
            self.page_size = page_size
            # scratch rows reshape to mb_scratch pages; tables carry one
            # extra write-overflow block (an idle slot's held-position
            # write may land one past max_len-1 — see _decode_chunk_fn)
            self.mb_scratch = max_len // page_size
            self.max_blocks = self.mb_scratch + 1
            self.num_pages = (num_pages if num_pages is not None
                              else num_slots * self.max_blocks)
            self._validate_pages()
            self._cache = model.init_paged_cache(
                num_slots, self.num_pages, page_size, self.max_blocks)
        else:
            self._cache = model.init_cache(num_slots, max_len)
        self._pcache = model.init_cache(self.prefill_batch, max_len)
        self.cache_allocations = 2

        S, cap = num_slots, max_new_cap
        self._dtok = jnp.zeros(S, jnp.int32)    # next input token
        self._dactive = jnp.zeros(S, bool)
        self._dgen = jnp.zeros(S, jnp.int32)    # tokens generated so far
        self._dlimit = jnp.zeros(S, jnp.int32)  # per-slot max_new_tokens
        self._dout = jnp.zeros((S, cap), jnp.int32)
        self._dbad = jnp.zeros(S, bool)         # NaN/inf poison flags

        self._place()
        self._compile()

        # device-dispatch wall histograms (repro.obs) — None keeps the
        # hot path at a single attribute check per dispatch
        self._m_admit = None
        self._m_decode = None
        if metrics is not None:
            self.bind_metrics(metrics)

    def bind_metrics(self, registry) -> None:
        """Register admit / decode-chunk host dispatch walls.  These
        are genuine wall-clock measurements of async dispatch overhead
        (not virtual-time), hence perf_counter rather than the engine
        clock."""
        bounds = (0.05, 0.1, 0.2, 0.5, 1.0, 2.0, 5.0,
                  10.0, 20.0, 50.0, 100.0)
        self._m_admit = registry.histogram(
            "executor_admit_dispatch_ms",
            "host wall of one prefill+commit dispatch", bounds)
        self._m_decode = registry.histogram(
            "executor_decode_dispatch_ms",
            "host wall of one K-step decode-chunk dispatch", bounds)

    def _validate_pages(self) -> None:
        per = self.num_pages // max(self.page_partitions, 1)
        if per < self.max_blocks:
            raise ValueError(
                f"num_pages={self.num_pages} over {self.page_partitions} "
                f"partition(s) leaves {per} pages per partition — fewer "
                f"than the {self.max_blocks} blocks one max_len request "
                f"needs; admission could never make progress")

    # -- layout hooks (overridden by ShardedExecutor) -------------------

    def _place(self) -> None:
        pass

    def _compile(self) -> None:
        if self.paged:
            self._gather = jax.jit(self._gather_fn, donate_argnums=(1,))
            self._prefill = jax.jit(self._prefill_paged_fn,
                                    donate_argnums=(1,))
            self._commit = jax.jit(self._commit_paged_fn,
                                   donate_argnums=(0, 2, 3, 4, 5, 6))
        else:
            self._prefill = jax.jit(self._prefill_fn, donate_argnums=(1,))
            self._commit = jax.jit(self._commit_fn,
                                   donate_argnums=(0, 2, 3, 4, 5, 6))
        self._decode = jax.jit(self._decode_chunk_fn,
                               donate_argnums=(1, 2, 3, 4, 6, 7))
        self._clear_flags = jax.jit(self._clear_flags_fn,
                                    donate_argnums=(0,))

    def _mesh_context(self):
        """Context the decode chunk is traced in (the sharded executor
        sets its mesh, under which the decode kernels run per shard)."""
        return contextlib.nullcontext()

    def _host_to_device(self, x: np.ndarray):
        return jnp.asarray(x)

    def _tokens_to_device(self, x: np.ndarray):
        """Upload one admission group's padded token rows (PB, plen).
        Split from `_host_to_device` so the sharded executor can lay
        the rows out like the prefill scratch."""
        return jnp.asarray(x)

    # -- jitted bodies --------------------------------------------------

    def _prefill_fn(self, params, pcache, tokens):
        logits, pcache = self.model.prefill(params, {"tokens": tokens},
                                            pcache, moe_fn=self.moe_fn,
                                            mla_absorb=self.mla_absorb)
        return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32), pcache

    def _commit_fn(self, cache, pcache, tok, active, gen, limit, out,
                   slots, firsts, limits):
        """Scatter the prefilled scratch rows into their slots and write
        the admission group's slot state.  Unused scratch rows carry
        slot index ``num_slots`` and are dropped by the scatter."""
        def ins(bdim):
            def f(big, small):
                idx = (slice(None),) * bdim + (slots,)
                return big.at[idx].set(small.astype(big.dtype),
                                       mode="drop")
            return f
        new = dict(cache)
        new["pos"] = cache["pos"].at[slots].set(pcache["pos"], mode="drop")
        # prefix leaves are (B, ...); block leaves are (n_blocks, B, ...)
        new["prefix"] = jax.tree_util.tree_map(ins(0), cache["prefix"],
                                               pcache["prefix"])
        new["blocks"] = jax.tree_util.tree_map(ins(1), cache["blocks"],
                                               pcache["blocks"])
        flags = (firsts != EOS) & (limits > 1)
        tok = tok.at[slots].set(firsts, mode="drop")
        active = active.at[slots].set(flags, mode="drop")
        gen = gen.at[slots].set(1, mode="drop")
        limit = limit.at[slots].set(limits, mode="drop")
        out = out.at[slots, 0].set(firsts, mode="drop")
        return new, tok, active, gen, limit, out

    # -- paged jitted bodies --------------------------------------------

    def _gather_fn(self, cache, pcache, src):
        """Copy shared prefix pages from the pool into the prefill
        scratch rows (copy-on-write borrow).  ``src`` is
        ``(PB, mb_scratch)`` int32 pool page ids; the sentinel
        ``num_pages`` leaves that scratch block untouched.  Reads the
        slot cache's pools, so it serializes behind any in-flight
        decode chunk — shared pages are never read mid-write."""
        NP, ps = self.num_pages, self.page_size
        PB, MBs = self.prefill_batch, self.mb_scratch
        flat = src.reshape(-1)
        valid = flat < NP
        safe = jnp.minimum(flat, NP - 1)

        def g(bdim):
            def f(scratch, pool):
                got = jnp.take(pool, safe, axis=bdim)
                lead = scratch.shape[:bdim]
                rest = scratch.shape[bdim + 2:]
                cur = scratch.reshape(lead + (PB * MBs, ps) + rest)
                m = valid.reshape((1,) * bdim + (PB * MBs,)
                                  + (1,) * (1 + len(rest)))
                return jnp.where(m, got.astype(scratch.dtype),
                                 cur).reshape(scratch.shape)
            return f
        new = dict(pcache)
        new["prefix"] = jax.tree_util.tree_map(g(0), pcache["prefix"],
                                               cache["prefix"])
        new["blocks"] = jax.tree_util.tree_map(g(1), pcache["blocks"],
                                               cache["blocks"])
        return new

    def _prefill_paged_fn(self, params, pcache, tokens, pos0):
        """Suffix prefill: rows start at absolute position ``pos0``
        (their shared prefix is already in the scratch via the page
        gather), so only the unique suffix runs through the model."""
        logits, pcache = self.model.prefill(
            params, {"tokens": tokens, "pos0": pos0}, pcache,
            moe_fn=self.moe_fn, mla_absorb=self.mla_absorb)
        return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32), pcache

    def _commit_paged_fn(self, cache, pcache, tok, active, gen, limit, out,
                         slots, firsts, limits, tables, wmask):
        """Scatter the prefilled scratch rows into their allocated
        pages and write the admission group's slot state + block
        tables.  ``wmask`` masks out shared (borrowed) blocks — only
        freshly written blocks land in the pool; masked / unused rows
        scatter to page id ``num_pages`` and are dropped."""
        NP, ps = self.num_pages, self.page_size
        PB, MBs = self.prefill_batch, self.mb_scratch
        new = dict(cache)
        new["pos"] = cache["pos"].at[slots].set(pcache["pos"], mode="drop")
        new["table"] = cache["table"].at[slots].set(tables, mode="drop")
        pages = jnp.where(wmask, tables[:, :MBs], NP).reshape(-1)

        def ins(bdim):
            def f(pool, scratch):
                lead = scratch.shape[:bdim]
                rest = scratch.shape[bdim + 2:]
                resh = scratch.reshape(lead + (PB * MBs, ps) + rest)
                idx = (slice(None),) * bdim + (pages,)
                return pool.at[idx].set(resh.astype(pool.dtype),
                                        mode="drop")
            return f
        new["prefix"] = jax.tree_util.tree_map(ins(0), cache["prefix"],
                                               pcache["prefix"])
        new["blocks"] = jax.tree_util.tree_map(ins(1), cache["blocks"],
                                               pcache["blocks"])
        flags = (firsts != EOS) & (limits > 1)
        tok = tok.at[slots].set(firsts, mode="drop")
        active = active.at[slots].set(flags, mode="drop")
        gen = gen.at[slots].set(1, mode="drop")
        limit = limit.at[slots].set(limits, mode="drop")
        out = out.at[slots, 0].set(firsts, mode="drop")
        return new, tok, active, gen, limit, out

    def _decode_chunk_fn(self, params, cache, tok, active, gen, limit, out,
                         bad):
        """`sync_every` decode steps over all slots, done-mask on device.

        With ``health_checks`` on, each step tests the step's final
        logits row for NaN/inf: a poisoned slot is deactivated in the
        same step (its garbage token is never written, ``gen`` does not
        advance) and its ``bad`` flag latches until the scheduler
        clears it — the rest of the batch decodes on untouched."""
        S, cap = out.shape
        sidx = jnp.arange(S)

        def step(carry, _):
            cache, tok, active, gen, out, bad = carry
            pos0 = cache["pos"]
            if self.paged:
                # idle slots must not scribble into pages that may have
                # been released and reassigned: park them at a position
                # past the block table so the paged write drops
                cache = dict(cache)
                cache["pos"] = jnp.where(
                    active, pos0, self.max_blocks * self.page_size)
            inp = jnp.where(active, tok, PAD)
            logits, cache = self.model.decode(
                params, {"tokens": inp[:, None]}, cache, moe_fn=self.moe_fn,
                mla_absorb=self.mla_absorb)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            if self.health_checks:
                row_bad = active & ~jnp.isfinite(logits[:, -1]).all(axis=-1)
                bad = bad | row_bad
                active = active & ~row_bad
            # hold position for idle slots (their kv write lands one past
            # their valid length and is masked / overwritten on admit)
            cache["pos"] = jnp.where(active, cache["pos"], pos0)
            # idle slots scatter out of bounds -> dropped
            wr = jnp.where(active, gen, cap)
            out = out.at[sidx, wr].set(nxt, mode="drop")
            gen = gen + active.astype(jnp.int32)
            active = active & (nxt != EOS) & (gen < limit)
            tok = jnp.where(active, nxt, tok)
            return (cache, tok, active, gen, out, bad), None

        carry, _ = jax.lax.scan(step, (cache, tok, active, gen, out, bad),
                                None, length=self.sync_every)
        return carry

    @staticmethod
    def _clear_flags_fn(arr, idx):
        """Clear boolean slot flags (active bits / poison flags)."""
        return arr.at[idx].set(False, mode="drop")

    # -- protocol -------------------------------------------------------

    def admit(self, tokens: np.ndarray, slot_idx: np.ndarray,
              limits: np.ndarray) -> None:
        """Prefill + insert + state commit for one admission group —
        pure async dispatch, no host sync.  The prefill program only
        touches the scratch cache, so it runs concurrently with any
        decode chunk already in flight; the insert/commit is serialized
        behind that chunk by its data dependency on the slot cache."""
        if self.paged:
            raise RuntimeError("paged executor: use admit_paged()")
        t0 = time.perf_counter() if self._m_admit is not None else 0.0
        firsts, self._pcache = self._prefill(
            self.params, self._pcache, self._tokens_to_device(tokens))
        (self._cache, self._dtok, self._dactive, self._dgen, self._dlimit,
         self._dout) = self._commit(
            self._cache, self._pcache, self._dtok, self._dactive,
            self._dgen, self._dlimit, self._dout,
            self._host_to_device(slot_idx), firsts,
            self._host_to_device(limits))
        if self._m_admit is not None:
            self._m_admit.observe((time.perf_counter() - t0) * 1e3)

    def admit_paged(self, tokens: np.ndarray, slot_idx: np.ndarray,
                    limits: np.ndarray, pos0: np.ndarray,
                    tables: np.ndarray, write_mask: np.ndarray,
                    gather_src: np.ndarray) -> None:
        """Paged admission: optional shared-page gather, suffix-only
        prefill from ``pos0``, then scatter the written pages into the
        pool and install the block tables.  ``tokens`` holds only the
        unique suffixes ``(PB, plen - p0)``; ``tables`` is
        ``(PB, max_blocks)``; ``write_mask`` ``(PB, mb_scratch)`` marks
        freshly written blocks; ``gather_src`` ``(PB, mb_scratch)``
        holds source pool pages (sentinel ``num_pages`` = no gather).
        Still pure async dispatch — but a gather reads the slot
        cache's pools, so cache-hit admissions serialize behind the
        in-flight decode chunk (miss admissions overlap as before)."""
        if not self.paged:
            raise RuntimeError("dense executor: use admit()")
        t0 = time.perf_counter() if self._m_admit is not None else 0.0
        if int(gather_src.min(initial=self.num_pages)) < self.num_pages:
            self._pcache = self._gather(
                self._cache, self._pcache,
                self._host_to_device(np.ascontiguousarray(gather_src)))
        firsts, self._pcache = self._prefill(
            self.params, self._pcache, self._tokens_to_device(tokens),
            self._host_to_device(pos0))
        (self._cache, self._dtok, self._dactive, self._dgen, self._dlimit,
         self._dout) = self._commit(
            self._cache, self._pcache, self._dtok, self._dactive,
            self._dgen, self._dlimit, self._dout,
            self._host_to_device(slot_idx), firsts,
            self._host_to_device(limits),
            self._host_to_device(np.ascontiguousarray(tables)),
            self._host_to_device(np.ascontiguousarray(write_mask)))
        if self._m_admit is not None:
            self._m_admit.observe((time.perf_counter() - t0) * 1e3)

    def _decode_args(self):
        return (self.params, self._cache, self._dtok, self._dactive,
                self._dgen, self._dlimit, self._dout, self._dbad)

    def decode_chunk(self) -> None:
        t0 = time.perf_counter() if self._m_decode is not None else 0.0
        with self._mesh_context():
            (self._cache, self._dtok, self._dactive, self._dgen,
             self._dout, self._dbad) = self._decode(*self._decode_args())
        if self._m_decode is not None:
            self._m_decode.observe((time.perf_counter() - t0) * 1e3)

    def compiled_decode_text(self) -> str:
        """HLO text of the compiled decode-chunk program, to check which
        kernels it runs (a Pallas kernel compiled for TPU shows up as a
        ``tpu_custom_call``; an interpreted one does not)."""
        with self._mesh_context():
            return self._decode.lower(
                *self._decode_args()).compile().as_text()

    def sync_control(self):
        """The every-K host sync: only the two tiny control arrays come
        back (np.array copies — device views are read-only)."""
        jax.block_until_ready((self._dactive, self._dgen))
        return np.array(self._dactive), np.array(self._dgen)

    def fetch_outputs(self) -> np.ndarray:
        return np.array(self._dout)

    # -- health / quarantine control ------------------------------------

    def slot_faults(self) -> np.ndarray:
        """Per-slot NaN/inf poison flags (host copy; blocks briefly —
        call right after ``sync_control``, when the chunk is done)."""
        return np.array(self._dbad)

    def deactivate(self, slots) -> None:
        """Clear active bits for the given slots (quarantine or
        mid-stream cancel) without touching their cache rows."""
        idx = np.asarray(list(slots), np.int32)
        if idx.size == 0:
            return
        self._dactive = self._clear_flags(self._dactive,
                                          self._host_to_device(idx))

    def clear_slot_faults(self, slots) -> None:
        idx = np.asarray(list(slots), np.int32)
        if idx.size == 0:
            return
        self._dbad = self._clear_flags(self._dbad,
                                       self._host_to_device(idx))


class ShardedExecutor(SingleDeviceExecutor):
    """dp×mp mesh executor: slots on ``data``, params on ``model``.

    The slot cache schema tags the slot dimension as the ``batch``
    logical axis, so :func:`repro.sharding.shardings_for_schema`
    resolves every cache leaf to a slot-on-``data`` placement (and, on
    an ``mp>1`` mesh, its ``kv_heads`` dim to the ``model`` axis); the
    control arrays and output buffer get the matching ``P("data")`` /
    ``P("data", None)`` layouts.  ``num_slots`` must divide the data
    axis size so every device owns the same number of slot rows.

    Params resolve through the same schema machinery (``fsdp=False``):
    attention heads, FFN, and vocab dims partition over the ``model``
    axis, so the prefill / insert+commit / decode-chunk programs run
    tensor-parallel under GSPMD — the fix for ``mp>1`` serve meshes
    silently replicating the full model per device.  The prefill
    scratch shards its rows over ``data`` when ``prefill_batch``
    divides the data-axis size, so batched prefill work partitions
    instead of replicating; the insert scatter all-gathers the scratch
    rows (each device writes only its own slots).

    Greedy decode is row-independent, so a 1-device mesh is
    token-identical to :class:`SingleDeviceExecutor`; dp-only and
    dp×mp meshes are token-identical by construction (verified by the
    forced-8-device ``dp=8`` and ``dp=4,mp=2`` parity tests).
    """

    def __init__(self, model, params, *, mesh: Mesh, **kw):
        self.mesh = mesh
        super().__init__(model, params, **kw)

    def _place(self) -> None:
        sizes = mesh_axis_sizes(self.mesh)
        dp = int(np.prod([sizes[a] for a in batch_axes(self.mesh)]) or 1)
        if self.num_slots % max(dp, 1) != 0:
            raise ValueError(
                f"num_slots={self.num_slots} must be divisible by the "
                f"mesh data-axis size {dp} to shard the slot dimension")
        self._rep = NamedSharding(self.mesh, P())
        # params: model-axis tensor parallel from the schema's logical
        # axes; slot cache + prefill scratch: batch dims on data,
        # kv-head dims on model (cache leaves carry "batch", so the
        # FSDP pass never touches them)
        self._param_sh = shardings_for_schema(self.model.schema, self.mesh,
                                              fsdp=False)
        if self.paged:
            # the page pool shards its page dim over data (each device
            # owns num_pages/dp pages) and kv-heads over model; the
            # host-side allocator partitions its free lists to match so
            # a slot's pages stay on the devices that own the slot row
            if self.num_pages % max(dp, 1) != 0:
                raise ValueError(
                    f"num_pages={self.num_pages} must be divisible by "
                    f"the mesh data-axis size {dp} to shard the pool")
            self.page_partitions = max(dp, 1)
            self._validate_pages()
            self._cache_sh = shardings_for_schema(
                self.model.paged_cache_schema(
                    self.num_slots, self.num_pages, self.page_size,
                    self.max_blocks), self.mesh)
        else:
            self._cache_sh = shardings_for_schema(
                self.model.cache_schema(self.num_slots, self.max_len),
                self.mesh)
        self._pcache_sh = shardings_for_schema(
            self.model.cache_schema(self.prefill_batch, self.max_len),
            self.mesh)
        # one tuple entry: the slot dim shards over ALL batch axes
        # (("pod","data") on multi-pod meshes — P("pod","data") would
        # wrongly assign them to two dims of a 1-D array)
        self._slot_sh = NamedSharding(self.mesh, P(batch_axes(self.mesh)))
        self._out_sh = NamedSharding(self.mesh,
                                     P(batch_axes(self.mesh), None))
        # admitted token rows + the prefill's first-token output ride
        # the scratch's row layout (replicated when PB doesn't divide)
        self._row2_sh = input_sharding(self.mesh, self.prefill_batch, 2)
        self._row1_sh = input_sharding(self.mesh, self.prefill_batch, 1)
        self.params = jax.device_put(self.params, self._param_sh)
        self._cache = jax.device_put(self._cache, self._cache_sh)
        self._pcache = jax.device_put(self._pcache, self._pcache_sh)
        self._dtok = jax.device_put(self._dtok, self._slot_sh)
        self._dactive = jax.device_put(self._dactive, self._slot_sh)
        self._dgen = jax.device_put(self._dgen, self._slot_sh)
        self._dlimit = jax.device_put(self._dlimit, self._slot_sh)
        self._dout = jax.device_put(self._dout, self._out_sh)
        self._dbad = jax.device_put(self._dbad, self._slot_sh)

    def _compile(self) -> None:
        s = self._slot_sh
        if self.paged:
            self._gather = jax.jit(
                self._gather_fn, donate_argnums=(1,),
                out_shardings=self._pcache_sh)
            self._prefill = jax.jit(
                self._prefill_paged_fn, donate_argnums=(1,),
                out_shardings=(self._row1_sh, self._pcache_sh))
            self._commit = jax.jit(
                self._commit_paged_fn, donate_argnums=(0, 2, 3, 4, 5, 6),
                out_shardings=(self._cache_sh, s, s, s, s, self._out_sh))
        else:
            self._prefill = jax.jit(
                self._prefill_fn, donate_argnums=(1,),
                out_shardings=(self._row1_sh, self._pcache_sh))
            self._commit = jax.jit(
                self._commit_fn, donate_argnums=(0, 2, 3, 4, 5, 6),
                out_shardings=(self._cache_sh, s, s, s, s, self._out_sh))
        self._decode = jax.jit(
            self._decode_chunk_fn, donate_argnums=(1, 2, 3, 4, 6, 7),
            out_shardings=(self._cache_sh, s, s, s, self._out_sh, s))
        self._clear_flags = jax.jit(self._clear_flags_fn,
                                    donate_argnums=(0,), out_shardings=s)

    def _mesh_context(self):
        return jax.set_mesh(self.mesh)

    def _host_to_device(self, x: np.ndarray):
        # small host control inputs (slot ids, limits) ride replicated
        return jax.device_put(x, self._rep)

    def _tokens_to_device(self, x: np.ndarray):
        return jax.device_put(x, self._row2_sh)
