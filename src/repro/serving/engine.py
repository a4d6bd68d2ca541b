"""ServeEngine: LM decode serving over the shared EngineCore.

A fixed batch of ``n_slots`` KV-cache slots (continuous batching):
requests join free slots as they arrive — mid-flight, no generational
barrier — get a *ragged* batched prefill (per-slot prompt lengths and
position ids), and one fused ``decode_step`` advances every active slot
per tick with per-slot cache indices.  Finished slots are recycled without
disturbing the others; the decode step stays one compiled executable.

Ragged prefill correctness: prompts are left-aligned with a zero pad
*suffix*, so causal attention keeps real tokens from ever attending pads;
per-slot last-token logits seed generation and the vector-``pos`` decode
path masks each slot's cache beyond its own length.  Dense/vlm families
are exact — matching per-request generation token-for-token (regression-
tested); moe is exact up to GShard expert-capacity effects (capacity is
derived from the *padded* length, which depends on who shares the prefill
bucket).  Recurrent families (ssm/hybrid) cannot mask a pad suffix out of
their state after the fact, so their admission is *length-bucketed*: each
tick's new prompts are grouped by exact length and prefilled with no pad
suffix at all (exact, regression-tested; one compiled prefill shape per
distinct prompt length).  ``generate()`` raises on ragged recurrent
batches instead of silently approximating.

The engine shares ``submit() / poll() / run_until_idle() / stats()`` with
:class:`repro.serving.CapsuleEngine` via :class:`repro.serving.EngineCore`
and takes the same pluggable schedulers (an SLO scheduler throttles
*admission concurrency* here; the decode shape is pinned by the caches).

Sharded decode: under a :class:`repro.serving.ShardedScheduler` the KV
caches themselves are sharded — the cache ``batch`` axis is the slot
axis, so the mesh's data-parallel devices each own ``n_slots /
n_devices`` cache rows for the whole decode (``lm.cache_shardings``),
params are replicated, and each tick's token/position vectors are placed
with the same rules.  Decode then runs SPMD: per-slot cache reads/writes
stay device-local, and results are bit-identical to the unsharded engine
(regression-tested on a 2-device mesh).  ``n_slots`` must divide evenly
over the mesh's batch-axis devices.

Streaming: requests submitted with ``stream=True`` additionally emit one
:class:`repro.serving.StreamEvent` per generated token (prompt tokens are
not echoed), drained via ``poll(stream=True)``; the final event carries
the :class:`Completion`.  Plain ``poll()`` stays completion-level.

Paged mode (``page_size=...``): the dense slot caches are replaced by a
:class:`repro.serving.pages.PagePool` — a global page pool with
per-slot page tables, a content-addressed prefix cache (shared prompt
prefixes prefill once; later requests pin the shared read-only pages
and prefill only their suffix via ``lm.continuation_prefill_step``),
optional int8 page quantization, reference-splice preemption (O(1), no
device traffic), and host-spill fallback when the pool runs dry.  The
unquantized paged engine is bit-identical to the dense one
(regression-tested); see ``docs/serving.md``.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import lm
from repro.models.common import LMConfig
from repro.serving.core import EngineCore, SlotTask
from repro.serving.schedulers import Scheduler, ShardedScheduler, pow2_bucket


@dataclasses.dataclass
class Request:
    prompt: List[int]
    max_new_tokens: int = 16
    temperature: float = 0.0      # 0 -> greedy
    rid: Optional[int] = None     # None -> engine-assigned
    stream: bool = False          # emit per-token StreamEvents
    priority: int = 0             # 0 = most urgent (PriorityScheduler)
    seed: Optional[int] = None    # None -> engine-derived at admission;
    #                               counter-based sampling makes temp>0
    #                               decode reproducible and slot-order
    #                               independent (see kernels.sampling)
    top_k: int = 0                # 0 -> no top-k restriction
    top_p: float = 1.0            # 1.0 -> no nucleus restriction


@dataclasses.dataclass
class Completion:
    rid: int
    tokens: List[int]             # prompt + generated
    latency_s: float = 0.0        # submit -> completion wall-clock


class ServeEngine(EngineCore):
    """Slot-based continuous-batching LM engine (one request per slot).

    Thread-safety follows :class:`repro.serving.EngineCore`: ``submit``
    may be called from any thread while ticks are in flight; ``tick`` /
    ``run_until_idle`` assume a single ticker thread.  Shape contracts:
    prompts are 1-D int token lists with ``0 < len < max_len``;
    completions carry ``prompt + generated`` tokens; stats count
    *generated* tokens as items.  Under a
    :class:`repro.serving.ShardedScheduler` the KV caches are sharded
    over the mesh's batch axes (slot-parallel) — ``n_slots`` must divide
    the mesh's batch-axis device count.
    """

    def __init__(self, cfg: LMConfig, params: Any, n_slots: int = 4,
                 max_len: int = 512, seed: int = 0,
                 scheduler: Optional[Scheduler] = None,
                 clock=time.perf_counter,
                 kernel_tune: Optional[bool] = None,
                 page_size: Optional[int] = None,
                 n_pages: Optional[int] = None,
                 quantize_pages: bool = False,
                 prefix_cache: bool = True,
                 decode_kernel: bool = False):
        assert cfg.family != "audio", "encoder models have no decode path"
        self._decode_kernel = bool(decode_kernel)
        if self._decode_kernel:
            # decode through the Pallas decode_attention kernel: dense
            # caches stay resident (int8 stays int8), and paged dense/moe
            # decode reads pages through the tables via scalar prefetch
            # instead of gathering a dense view (see _decode_paged_impl);
            # tokens are drawn on device by the fused_sampling kernel
            cfg = dataclasses.replace(cfg, decode_impl="pallas")
        self.cfg = cfg
        self.params = params
        self.n_slots = n_slots
        self.max_len = max_len
        # recurrent state (ssm/hybrid) cannot mask a pad suffix the way
        # attention masks cache rows: admission is length-bucketed instead
        self._recurrent = cfg.family in ("ssm", "hybrid")
        self._seed0 = int(seed)       # base of engine-derived request seeds
        self._prefix_cache = bool(prefix_cache)
        if page_size is not None:
            from repro.serving.pages import PagePool

            self._pages: Optional[Any] = PagePool(
                cfg, n_slots, max_len, page_size, n_pages=n_pages,
                quantize=quantize_pages)
        else:
            self._pages = None
        # tick programs are named functions, so a device trace shows
        # jit_lm_decode, jit_lm_prefill, ... rather than jit__lambda_
        def lm_decode(p, t, pos, c):
            return lm.decode_step(p, cfg, {"tokens": t, "pos": pos}, c)

        def lm_prefill(p, t, ln, idx, c):
            return self._prefill_scatter(p, t, ln, idx, c)

        def lm_gather_rows(idx, c):
            return lm.gather_cache_rows(cfg, idx, c)

        def lm_inject_rows(rows, idx, c):
            return lm.scatter_cache_rows(cfg, idx, rows, c)

        self._decode = jax.jit(lm_decode)
        self._prefill = jax.jit(lm_prefill)
        # slot-axis cache row movement (shared by preemption/resume here
        # and by the disaggregated engines, which subclass this one)
        self._gather = jax.jit(lm_gather_rows)
        self._inject = jax.jit(lm_inject_rows)
        # paged kernel decode needs per-slot tables threaded into the
        # model; vlm keeps the gather-to-dense fallback (its per-site kv
        # slicing predates pool-shaped leaves)
        self._paged_kernel = (self._decode_kernel
                              and self._pages is not None
                              and cfg.family in ("dense", "moe"))
        if self._pages is not None:
            def lm_decode_paged(p, t, pos, tb, pool, res):
                return self._decode_paged_impl(p, t, pos, tb, pool, res)

            def lm_prefill_paged(off, p, t, ln, pmap, pref, idx, pool,
                                 res):
                return self._prefill_paged_impl(off, p, t, ln, pmap, pref,
                                                idx, pool, res)

            self._decode_paged = jax.jit(lm_decode_paged)
            self._prefill_paged = jax.jit(lm_prefill_paged,
                                          static_argnums=(0,))
        super().__init__(capacity=n_slots, scheduler=scheduler, clock=clock,
                         kernel_tune=kernel_tune)
        if self._pages is not None:
            # paged mode never allocates the dense slot caches — that is
            # the whole point (resident capacity bounded by pages, not
            # slots x max_len); generate() builds its own fresh caches
            self._caches = None
            self._pool = self._pages.init_pool_arrays()
            self._residual = self._pages.init_residual_arrays()
        else:
            self._caches = lm.make_caches(cfg, n_slots, max_len)
        self._tok = np.zeros((n_slots,), np.int32)   # pending token per slot
        self._pos = np.zeros((n_slots,), np.int32)   # its cache index
        if isinstance(self.scheduler, ShardedScheduler):
            self._shard_state(self.scheduler)

    def _shard_state(self, sched: ShardedScheduler) -> None:
        """Pin the decode state onto the scheduler's mesh: params
        replicated (decode wants weights stationary), KV caches sharded
        along their slot (``batch``) axis via ``lm.cache_shardings`` so
        each data-parallel device owns ``n_slots / n_devices`` slots end
        to end.  Per-tick token/position arrays follow through
        ``scheduler.place()``; the jitted prefill-scatter and decode
        steps then run SPMD with device-local cache updates."""
        from jax.sharding import NamedSharding, PartitionSpec

        self.params = jax.device_put(
            self.params, NamedSharding(sched.mesh, PartitionSpec()))
        if self._pages is not None:
            # the pool's page axis keeps the logical name "batch", so the
            # same shape-aware rules that shard slots shard pages; the
            # pool's block-preferring allocator then keeps a slot's pages
            # on the device that owns the slot's decode rows
            from repro.parallel import sharding as sharding_lib

            self._pool = jax.device_put(
                self._pool, sharding_lib.shardings_for(
                    self._pool, self._pages.pool_specs(),
                    sched.rules, sched.mesh))
            self._residual = jax.device_put(
                self._residual, sharding_lib.shardings_for(
                    self._residual, self._pages.residual_specs(),
                    sched.rules, sched.mesh))
            self._pages.set_device_blocks(sched.n_devices)
            return
        self._caches = jax.device_put(
            self._caches, lm.cache_shardings(self.cfg, self._caches,
                                             sched.mesh, sched.rules))

    def _prefill_scatter(self, params, tokens, lengths, slot_idx, caches):
        """Prefill a (bucketed) sub-batch on fresh caches, then scatter its
        rows into the engine caches at ``slot_idx`` — admission cost scales
        with the number of admitted slots, not engine capacity."""
        sub = lm.make_caches(self.cfg, tokens.shape[0], self.max_len)
        logits, sub = lm.ragged_prefill_step(
            params, self.cfg, {"tokens": tokens, "lengths": lengths}, sub)
        return logits, lm.scatter_cache_rows(self.cfg, slot_idx, sub, caches)

    def _decode_paged_impl(self, params, tok, pos, tables, pool, residual):
        """One paged decode tick.

        Kernel path (``decode_kernel=True``, dense/moe): the pool leaves
        pass straight through (:meth:`PagePool.pool_tree`, no gather) and
        the decode_attention kernel reads each slot's resident pages
        through its table row via scalar prefetch, writing the fresh row
        in place — a slot touches only its own pages instead of the full
        gathered ``(n_slots, max_len)`` view.

        Fallback: gather the dense view through the page tables, run the
        ordinary ``lm.decode_step``, scatter each slot's new row back
        into its mapped page.  Residual (non-paged) leaves are read-only
        during decode."""
        if self._paged_kernel:
            tree = self._pages.pool_tree(pool, residual)
            logits, new_tree = lm.decode_step(
                params, self.cfg, {"tokens": tok, "pos": pos}, tree,
                paged_tables=tables)
            new_pool, _ = self._pages.pool_untree(new_tree)
            return logits, new_pool
        view = self._pages.build_view(pool, residual, tables)
        logits, new_view = lm.decode_step(
            params, self.cfg, {"tokens": tok, "pos": pos}, view)
        return logits, self._pages.scatter_decode_rows(
            pool, new_view, tables, pos)

    def _prefill_paged_impl(self, off, params, tokens, lengths, page_map,
                            prefix_rows, slot_idx, pool, residual):
        """Paged (possibly continuation) prefill: a fresh sub cache just
        long enough for the written page span, prefilled from position
        ``off`` (0 = ordinary ragged prefill; > 0 continues from the
        dequantized shared-prefix pages in ``prefix_rows``), then
        scattered into the pool at page granularity."""
        pages = self._pages
        nb = tokens.shape[0]
        total = off + page_map.shape[1] * pages.page_size
        if off == 0:
            sub = lm.make_caches(self.cfg, nb, total)
            logits, sub = lm.ragged_prefill_step(
                params, self.cfg, {"tokens": tokens, "lengths": lengths},
                sub)
        else:
            sub = pages.make_continuation_caches(pool, prefix_rows, nb,
                                                 total)
            logits, sub = lm.continuation_prefill_step(
                params, self.cfg, {"tokens": tokens, "lengths": lengths},
                sub, off)
        new_pool = pages.write_prefill_pages(pool, sub, page_map, off)
        new_res = pages.scatter_residual_rows(
            residual, pages.residual_rows_from(sub), slot_idx)
        return logits, new_pool, new_res

    # -- sampling ----------------------------------------------------------
    #
    # Counter-based (see repro.kernels.sampling): every draw is a pure
    # function of (request seed, sequence position of the drawn token),
    # so temperature>0 decode is reproducible and independent of batch
    # composition, slot assignment, preemption, and disagg handoffs.
    # Greedy stays an exact raw-logits argmax on every path.

    def _bind_seed(self, task: SlotTask) -> int:
        """The request's sampling seed, materialized at admission: a
        request without an explicit seed gets one derived from the
        engine seed and its rid, written back onto the request so it
        survives preemption and travels with a disagg handoff."""
        req = task.payload
        seed = getattr(req, "seed", None)
        if seed is None:
            seed = (self._seed0 ^ ((task.rid + 1) * 0x9E3779B1)) & 0x7FFFFFFF
            req.seed = seed             # guarded-by: single ticker thread
        return int(seed)

    def _sample_row(self, logits_row: np.ndarray, temperature: float,
                    seed: int, pos: int, top_k: int = 0,
                    top_p: float = 1.0) -> int:
        from repro.kernels.sampling import sample_token_host

        return sample_token_host(logits_row, temperature, seed, pos,
                                 top_k=top_k, top_p=top_p)

    def _sample_task_row(self, logits_row: np.ndarray, task: SlotTask,
                         pos: int) -> int:
        req = task.payload
        return self._sample_row(
            logits_row, float(getattr(req, "temperature", 0.0)),
            self._bind_seed(task), pos,
            top_k=int(getattr(req, "top_k", 0) or 0),
            top_p=float(getattr(req, "top_p", 1.0)))

    def _sample_batch_device(self, logits, active, pos_of) -> np.ndarray:
        """Kernel-path sampling: one fused_sampling launch draws every
        active slot's token on device; the only host transfer of the
        tick is the (n_slots,) int32 token vector — the full (B, V)
        logits never leave the device."""
        from repro import kernels

        n = self._tok.shape[0]
        temp = np.zeros((n,), np.float32)
        seeds = np.zeros((n,), np.uint32)
        poss = np.zeros((n,), np.int32)
        tks = np.zeros((n,), np.int32)
        tps = np.ones((n,), np.float32)
        for s, task in active:
            req = task.payload
            temp[s] = float(getattr(req, "temperature", 0.0))
            seeds[s] = self._bind_seed(task)
            poss[s] = pos_of(s)
            tks[s] = int(getattr(req, "top_k", 0) or 0)
            tps[s] = float(getattr(req, "top_p", 1.0))
        return np.asarray(jax.block_until_ready(kernels.fused_sampling(
            logits, temp, seeds, poss, top_k=tks, top_p=tps, tune=False)))

    # -- single-batch convenience ------------------------------------------

    def generate(self, prompts: List[List[int]], max_new_tokens: int = 16,
                 temperature: float = 0.0, seed: Optional[int] = None,
                 top_k: int = 0, top_p: float = 1.0) -> List[List[int]]:
        """Batched prefill + greedy/temperature decode — ragged-correct:
        each prompt keeps its own length and position ids, so the result
        matches per-request generation (attention-cached families).

        Temperature>0 draws are counter-based: row ``i`` samples with
        seed ``(base ^ ((i + 1) * 0x9E3779B1)) & 0x7FFFFFFF`` (base =
        ``seed`` or the engine seed) and counter = the token's sequence
        position, so repeated calls are reproducible."""
        b = len(prompts)
        for p in prompts:
            self._check_prompt(p)
        if max_new_tokens <= 0:
            return [list(p) for p in prompts]
        plens = sorted({len(p) for p in prompts})
        if self._recurrent and len(plens) > 1:
            raise ValueError(
                f"ragged prompts (lengths {plens}) in one generate() batch "
                f"would fold pad tokens into the recurrent "
                f"({self.cfg.family}) state; pass uniform-length prompts, "
                f"or submit() them — the engine admits recurrent prompts "
                f"in exact-length buckets")
        caches = lm.make_caches(self.cfg, b, self.max_len)
        # recurrent: no pad suffix at all (exact length); attention
        # families mask the pad, so pow2 bucketing is free
        plen = (plens[-1] if self._recurrent
                else pow2_bucket(max(plens), self.max_len))
        tokens = np.zeros((b, plen), np.int32)
        lengths = np.ones((b,), np.int32)
        for i, p in enumerate(prompts):
            tokens[i, :len(p)] = p                   # left-aligned, pad right
            lengths[i] = len(p)
        logits, caches = self._prefill(
            self.params, jnp.asarray(tokens), jnp.asarray(lengths),
            jnp.arange(b), caches)
        logits = np.asarray(jax.block_until_ready(logits))
        out = [list(p) for p in prompts]
        base = self._seed0 if seed is None else int(seed)
        row_seed = [(base ^ ((i + 1) * 0x9E3779B1)) & 0x7FFFFFFF
                    for i in range(b)]
        pos = lengths.copy()
        alive = np.ones((b,), bool)           # slots still within max_len
        for k in range(max_new_tokens):
            for i in range(b):
                if alive[i]:
                    out[i].append(self._sample_row(
                        logits[i], temperature, row_seed[i], int(pos[i]),
                        top_k=top_k, top_p=top_p))
            if k == max_new_tokens - 1:
                break
            alive &= pos < self.max_len       # per-slot stop (cache full)
            if not alive.any():
                break
            nxt = np.array([out[i][-1] if alive[i] else 0
                            for i in range(b)], np.int32)
            logits, caches = self._decode(
                self.params, jnp.asarray(nxt[:, None]),
                jnp.asarray(np.minimum(pos, self.max_len - 1)), caches)
            logits = np.asarray(jax.block_until_ready(logits))
            pos += 1
        return out

    # -- workload hooks ----------------------------------------------------

    def _check_prompt(self, prompt) -> None:
        if not len(prompt):
            raise ValueError("empty prompt")
        if len(prompt) >= self.max_len:
            raise ValueError(
                f"prompt length {len(prompt)} leaves no room to generate "
                f"(max_len={self.max_len})")

    def _expand(self, request: Request
                ) -> Tuple[List[SlotTask], Dict[str, Any]]:
        prompt = [int(t) for t in request.prompt]
        request.prompt = prompt
        self._check_prompt(prompt)
        if request.max_new_tokens <= 0:
            return [], {}                 # prefill-free identity completion
        return [SlotTask(payload=request)], {}

    def _admit(self, new: List[Tuple[int, SlotTask]]
               ) -> Tuple[List[int], int]:
        """Ragged batched prefill for the newly admitted slots only: a
        pow2-bucketed sub-batch (cost scales with admissions, not engine
        capacity) whose cache rows are scattered into the slot caches.

        Recurrent families (ssm/hybrid) get *length-bucketed admission*
        instead: the new tasks are grouped by exact prompt length and each
        group prefills with zero pad suffix, because a recurrent state —
        unlike a KV cache — cannot mask pad tokens out after the fact.
        This closes the documented ragged-prefill gap (recurrent serving
        is exact, regression-tested) at the cost of one compiled prefill
        shape per distinct prompt length seen.

        Tasks previously preempted (``_evict`` saved their cache rows)
        take the *resume* path instead of prefilling again: one batched
        scatter re-injects their rows at the new slots and decode
        continues from the saved token/position — the finished sequence
        is exactly what an un-preempted run produces.
        """
        if self._pages is not None:
            return self._admit_paged(new)
        resume = [(s, t) for s, t in new if "resume_rows" in t.state]
        new = [(s, t) for s, t in new if "resume_rows" not in t.state]
        pre_finished: List[int] = []
        if resume:
            rows = lm.concat_cache_rows(
                self.cfg, [t.state.pop("resume_rows") for _, t in resume])
            self._caches = self._inject(
                self._place_rows(rows),
                self.scheduler.place(
                    np.asarray([s for s, _ in resume], np.int32)),
                self._caches)
            for s, task in resume:
                self._tok[s] = task.state.pop("resume_tok")
                self._pos[s] = task.state.pop("resume_pos")
                if task.state["left"] <= 0 or self._pos[s] >= self.max_len:
                    pre_finished.append(s)
        if not new:
            return pre_finished, 0
        if self._recurrent:
            groups: Dict[int, List[Tuple[int, SlotTask]]] = {}
            for s, task in new:
                groups.setdefault(len(task.payload.prompt),
                                  []).append((s, task))
            finished: List[int] = list(pre_finished)
            for plen in sorted(groups):
                finished += self._prefill_group(groups[plen], plen)
            return finished, len(new)
        plen = pow2_bucket(
            max(len(t.payload.prompt) for _, t in new), self.max_len)
        return pre_finished + self._prefill_group(new, plen), len(new)

    def _prefill_group(self, new: List[Tuple[int, SlotTask]], plen: int
                       ) -> List[int]:
        """Prefill one sub-batch whose prompts all fit in ``plen``."""
        nb = pow2_bucket(len(new), self.capacity)
        self._maybe_tune_prefill(nb, plen)
        tokens = np.zeros((nb, plen), np.int32)
        lengths = np.ones((nb,), np.int32)
        slot_idx = np.full((nb,), self.capacity, np.int32)  # pad rows: OOB
        for i, (s, task) in enumerate(new):
            p = task.payload.prompt
            tokens[i, :len(p)] = p
            lengths[i] = len(p)
            slot_idx[i] = s
        place = self.scheduler.place
        logits, self._caches = self._prefill(
            self.params, place(tokens), place(lengths),
            place(slot_idx), self._caches)
        logits = np.asarray(jax.block_until_ready(logits))
        finished = []
        for i, (s, task) in enumerate(new):
            req = task.payload
            tok = self._sample_task_row(logits[i], task, int(lengths[i]))
            task.state = {"out": list(req.prompt) + [tok],
                          "left": req.max_new_tokens - 1}
            self._emit(task.rid, tok)
            self._tok[s] = tok
            self._pos[s] = lengths[i]
            if task.state["left"] <= 0 or self._pos[s] >= self.max_len:
                finished.append(s)
        return finished

    # -- paged-cache admission / lifecycle ---------------------------------

    def _admit_paged(self, new: List[Tuple[int, SlotTask]]
                     ) -> Tuple[List[int], int]:
        """Paged admission.  Resumed tasks splice their saved table row
        back (or re-import a host spill); fresh tasks look their prompt
        up in the prefix index and prefill only past the longest hit.

        Fresh tasks are processed in waves of equal prefix-hit length,
        shortest first, and a wave defers any task whose *next* page
        hash duplicates a groupmate's — that page registers when the
        representative's group prefills, so the deferred task re-checks
        and picks the hit up.  Two identical system prompts submitted in
        the same tick therefore still prefill the shared span exactly
        once."""
        pages = self._pages
        ps = pages.page_size
        resume = [(s, t) for s, t in new
                  if "resume_pages" in t.state or "resume_spill" in t.state]
        resumed = {id(t) for _, t in resume}
        fresh = [(s, t) for s, t in new if id(t) not in resumed]
        pre_finished: List[int] = []
        for s, task in resume:
            if "resume_spill" in task.state:
                payload, n = task.state.pop("resume_spill")
                pgs = self._alloc_pages(n, s)
                self._pool = pages.import_pages(self._pool, payload, pgs)
            else:
                pgs = task.state.pop("resume_pages")
            pages.bind_slot(s, pgs)
            self._tok[s] = task.state.pop("resume_tok")
            self._pos[s] = task.state.pop("resume_pos")
            if task.state["left"] <= 0 or self._pos[s] >= self.max_len:
                pre_finished.append(s)
        if not fresh:
            return pre_finished, 0
        infos: List[List[Any]] = []
        for s, task in fresh:
            hashes = (pages.chain_hashes(task.payload.prompt)
                      if self._prefix_cache else [])
            hits = pages.acquire_prefix(hashes) if hashes else []
            infos.append([s, task, hashes, hits])
        finished = list(pre_finished)
        while infos:
            min_hit = min(len(info[3]) for info in infos)
            group, defer, seen_next = [], [], set()
            for info in infos:
                if len(info[3]) != min_hit:
                    defer.append(info)
                    continue
                nxt = info[2][min_hit] if min_hit < len(info[2]) else None
                if nxt is not None and nxt in seen_next:
                    defer.append(info)
                    continue
                if nxt is not None:
                    seen_next.add(nxt)
                group.append(info)
            finished += self._prefill_paged_group(group, min_hit * ps)
            for info in defer:   # hits can only grow as groups register
                info[3] += pages.extend_prefix(info[2], len(info[3]))
            infos = defer
        return finished, len(fresh)

    def _prefill_paged_group(self, group: List[List[Any]], off: int
                             ) -> List[int]:
        """Prefill one wave of tasks sharing prefix-hit length ``off``
        (0 = full prefill).  Suffixes pad to the dense engine's pow2
        bucket (so full prefills stay bit-identical to the dense path),
        pages past each task's own span map to the drop sentinel, and
        full suffix pages register into the prefix index."""
        pages = self._pages
        ps = pages.page_size
        nb = pow2_bucket(len(group), self.capacity)
        smax = max(len(info[1].payload.prompt) - off for info in group)
        splen = pow2_bucket(smax, self.max_len - off)
        npg = -(-splen // ps)
        if off == 0:
            self._maybe_tune_prefill(nb, splen)
        tokens = np.zeros((nb, splen), np.int32)
        lengths = np.ones((nb,), np.int32)
        slot_idx = np.full((nb,), self.capacity, np.int32)  # pad rows: OOB
        page_rows = np.full((nb, npg), pages.n_pages, np.int32)
        prefix_rows = np.zeros((nb, off // ps), np.int32)
        hit_reqs = hit_pages = 0
        for i, (s, task, hashes, hits) in enumerate(group):
            p = task.payload.prompt
            suffix = p[off:]
            tokens[i, :len(suffix)] = suffix
            lengths[i] = len(suffix)
            slot_idx[i] = s
            # pages covering positions [0, len(p)] — the prompt plus the
            # first decode write; later pages allocate lazily in _step
            own = self._alloc_pages(len(p) // ps + 1 - len(hits), s)
            allp = list(hits) + own
            pages.bind_slot(s, allp)
            for j in range(len(hits), len(hashes)):
                pages.register_hash(allp[j], hashes[j])
            base = off // ps
            for j in range(min(npg, len(allp) - base)):
                page_rows[i, j] = allp[base + j]
            prefix_rows[i, :] = allp[:base]
            if hits:
                hit_reqs += 1
                hit_pages += len(hits)
        place = self.scheduler.place
        logits, self._pool, self._residual = self._prefill_paged(
            off, self.params, place(tokens), place(lengths),
            jnp.asarray(page_rows), jnp.asarray(prefix_rows),
            place(slot_idx), self._pool, self._residual)
        logits = np.asarray(jax.block_until_ready(logits))
        finished = []
        for i, (s, task, hashes, hits) in enumerate(group):
            req = task.payload
            tok = self._sample_task_row(logits[i], task, len(req.prompt))
            task.state = {"out": list(req.prompt) + [tok],
                          "left": req.max_new_tokens - 1}
            self._emit(task.rid, tok)
            self._tok[s] = tok
            self._pos[s] = len(req.prompt)
            if task.state["left"] <= 0 or self._pos[s] >= self.max_len:
                finished.append(s)
        self._count_pages(
            prefill_ticks=1, prefix_hits=hit_reqs,
            prefix_pages_hit=hit_pages,
            prefill_tokens=sum(len(info[1].payload.prompt) - off
                               for info in group))
        return finished

    def _alloc_pages(self, n: int, slot: int) -> List[int]:
        """Allocate with spill fallback: when the pool is dry, preempted
        (queued) requests' pages move to host memory and free up —
        admission pressure never crashes a losslessly preempted task."""
        if n <= 0:
            return []
        from repro.serving.pages import PagePoolExhausted

        try:
            return self._pages.allocate(n, slot)
        except PagePoolExhausted:
            if not self._spill_preempted():
                raise
            return self._pages.allocate(n, slot)

    def _spill_preempted(self) -> bool:
        """Export every queued preempted task's pages to host numpy and
        release them; resume re-imports into fresh pages.  Returns
        whether anything was spilled."""
        with self._lock:
            targets = [t for t in self._queue if "resume_pages" in t.state]
        spilled = 0
        for task in targets:
            pgs = task.state.pop("resume_pages")
            payload = jax.tree.map(np.asarray, jax.block_until_ready(
                self._pages.export_pages(self._pool, pgs)))
            task.state["resume_spill"] = (payload, len(pgs))
            self._pages.release(pgs)
            spilled += len(pgs)
        if spilled:
            self._count_pages(spilled_pages=spilled)
        return spilled > 0

    def _ensure_decode_pages(self, active: List[Tuple[int, SlotTask]]
                             ) -> None:
        """Allocate the page under each active slot's write head when the
        decode position crosses a page boundary."""
        pages = self._pages
        for s, _ in active:
            idx = int(self._pos[s]) // pages.page_size
            if pages.page_at(s, idx) < 0:
                pages.set_slot_page(s, idx, self._alloc_pages(1, s)[0])

    def _release_slot(self, slot: int, task: SlotTask) -> None:
        if getattr(self, "_pages", None) is not None:
            pgs = self._pages.unbind_slot(slot)
            if pgs:
                self._pages.release(pgs)

    def _count_pages(self, **counts: int) -> None:
        with self._lock:
            d = self._stats.pages
            for k, v in counts.items():
                d[k] = d.get(k, 0) + int(v)

    def pin_page_hashes(self, hashes: List[Optional[bytes]]
                        ) -> Dict[int, int]:
        """Pin prefix-index hits on this engine's pool (empty when not
        paged) — the disaggregated front-end's handoff-dedup probe."""
        if self._pages is None:
            return {}
        return self._pages.pin_hashes(hashes)

    def release_page_pins(self, pages: List[int]) -> None:
        """Drop references taken by :meth:`pin_page_hashes` — the
        front-end's failed-delivery unwind."""
        if self._pages is not None and pages:
            self._pages.release(pages)

    @property
    def paged(self) -> bool:
        return self._pages is not None

    @property
    def free_pages(self) -> Optional[int]:
        """Allocatable pages right now (None when not paged) — the
        admission-control backpressure gauge."""
        return self._pages.free_pages if self._pages is not None else None

    @property
    def total_pages(self) -> Optional[int]:
        return self._pages.total_pages if self._pages is not None else None

    def _batch_for(self, n_active: int) -> int:
        return self.capacity            # decode shape pinned by the caches

    def _place_rows(self, rows: Any) -> Any:
        """Cache rows about to scatter into (possibly sharded) slot
        caches: replicate onto the scheduler's mesh so the jitted
        scatter stays device-local per slot shard."""
        if isinstance(self.scheduler, ShardedScheduler):
            from repro.parallel.sharding import replicated_shardings

            return jax.device_put(
                rows, replicated_shardings(rows, self.scheduler.mesh))
        return rows

    def _evict(self, slot: int, task: SlotTask) -> None:
        """Lossless preemption: gather the slot's cache rows (the same
        slot-axis gather a :class:`repro.serving.CacheHandoff` uses) plus
        the pending token/position into ``task.state``; the generated
        tokens already live there (``state["out"]``).  ``_admit`` later
        re-injects the rows at whatever slot the task lands in and the
        decode continues exactly where it stopped.

        Paged mode preempts by *reference*: the task keeps its page
        ownership and only the table row is saved — O(1), no device
        gather; resume splices the row into the new slot.  (If the pool
        later runs dry, ``_spill_preempted`` demotes the references to a
        host copy — still lossless.)"""
        if self._pages is not None:
            task.state["resume_pages"] = self._pages.unbind_slot(slot)
            task.state["resume_tok"] = int(self._tok[slot])
            task.state["resume_pos"] = int(self._pos[slot])
            return
        task.state["resume_rows"] = jax.block_until_ready(
            self._gather(jnp.asarray([slot], jnp.int32), self._caches))
        task.state["resume_tok"] = int(self._tok[slot])
        task.state["resume_pos"] = int(self._pos[slot])

    def _maybe_tune_prefill(self, nb: int, plen: int) -> None:
        """Measured flash-attention tuning for one exact prefill bucket
        (``kernel_tune=True`` engines only).

        Prefill shapes depend on traffic (sub-batch and prompt-length
        buckets), so guessing them at warm-up would tune buckets the
        runtime never hits.  ``_admit`` runs eagerly, before the jitted
        prefill traces — the first admission at a new ``(nb, plen)``
        bucket measures with concrete arrays here, and the trace that
        immediately follows freezes the cached winner in.  Later
        admissions in the same bucket hit the cache and pay nothing.
        """
        if not self.kernel_tune or self.cfg.attn_impl != "pallas":
            return
        from repro.kernels import tuning as ktuning
        from repro.kernels.registry import registry as kernel_registry

        kspec = kernel_registry.get("flash_attention")
        if not kspec.is_available():
            return
        cfg = self.cfg
        # measure in the model's compute dtype: the cache key includes
        # the dtype, and the traced prefill dispatches q/k/v in cdtype
        cd = cfg.cdtype()
        q = jax.random.normal(
            jax.random.key(0), (nb, plen, cfg.n_heads, cfg.head_dim)
        ).astype(cd)
        k = jax.random.normal(
            jax.random.key(1), (nb, plen, cfg.n_kv_heads, cfg.head_dim)
        ).astype(cd)
        v = jax.random.normal(
            jax.random.key(2), (nb, plen, cfg.n_kv_heads, cfg.head_dim)
        ).astype(cd)
        cache = ktuning.default_cache()
        if cache.get(ktuning.cache_key_for(kspec, (q, k, v))) is None:
            t0 = time.perf_counter()
            ktuning.autotune(
                kspec, (q, k, v),
                {"causal": True, "softmax_mode": cfg.softmax_mode},
                cache=cache)
            # one-off measurement, not serving time: keep it out of the
            # tick wall the SLO scheduler and throughput stats observe
            self._exclude_tick_time(time.perf_counter() - t0)

    def _step(self, active: List[Tuple[int, SlotTask]], n_batch: int
              ) -> Tuple[List[int], int]:
        place = self.scheduler.place
        if self._pages is not None:
            self._ensure_decode_pages(active)
            logits, self._pool = self._decode_paged(
                self.params, place(self._tok[:, None]), place(self._pos),
                jnp.asarray(self._pages.tables_snapshot()),
                self._pool, self._residual)
        else:
            logits, self._caches = self._decode(
                self.params, place(self._tok[:, None]),
                place(self._pos), self._caches)
        if self._decode_kernel:
            # fused on-device sampling: only the (n_slots,) token vector
            # crosses to host; each sampled token's counter is the
            # position it will occupy (pos + 1)
            toks = self._sample_batch_device(
                logits, active, lambda s: int(self._pos[s]) + 1)
        else:
            logits = np.asarray(jax.block_until_ready(logits))
        finished = []
        for s, task in active:
            if self._decode_kernel:
                nxt = int(toks[s])
            else:
                nxt = self._sample_task_row(logits[s], task,
                                            int(self._pos[s]) + 1)
            task.state["out"].append(nxt)
            task.state["left"] -= 1
            self._emit(task.rid, nxt)
            self._pos[s] += 1
            self._tok[s] = nxt
            if task.state["left"] <= 0 or self._pos[s] >= self.max_len:
                finished.append(s)
        return finished, len(active)

    def _request_class(self, request: Request) -> str:
        """Latency histogram key: prompts bucketed to powers of two, so
        p50/p95 are reported per prefill-cost class (``"lm/p8"`` = prompt
        length in (4, 8])."""
        return f"lm/p{pow2_bucket(len(request.prompt), self.max_len)}"

    def _finalize(self, entry, latency_s: float) -> Completion:
        tokens = (entry.tasks[0].state["out"] if entry.tasks
                  else list(entry.request.prompt))   # max_new_tokens <= 0
        return Completion(rid=entry.request.rid, tokens=tokens,
                          latency_s=latency_s)

    def stats(self):
        """Engine stats; paged engines additionally merge the pool's
        allocation/eviction/pin counters into ``stats().pages`` next to
        the engine-side prefill/prefix-hit counters."""
        st = super().stats()
        if self._pages is not None:
            merged = self._pages.counters()
            merged.update(st.pages)
            st.pages = merged
        return st
