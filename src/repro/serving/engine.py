"""Continuous-batching inference engine (paper §3.1's service model,
realized in JAX).

One engine == one pool's GPU: ``n_max`` KV slots advance in lockstep;
each ``step()`` is one iteration (one decode token for every active
slot). Prefill is chunked at ``c_chunk`` tokens per iteration
(Sarathi-style), matching E[S] = (ceil(L_in/C_chunk) + L_out) * t_iter.

The step path is FIXED-SHAPE (see DESIGN.md §Engine):

  * one jitted decode trace, total — a per-slot active mask makes
    empty / mid-prefill slots provable bitwise no-ops on the cache
    (the continuous-batching correctness invariant);
  * prefill chunks are padded to a small set of bucketed lengths
    (powers of two up to ``c_chunk``), so the number of compiled
    prefill traces is bounded by the bucket count, independent of the
    request-length mix — no per-request recompiles;
  * every slot with a pending chunk advances in ONE jitted call per
    iteration (batched multi-slot prefill with in-place
    dynamic_update_slice on the batched cache), not one call per slot.

The hot path is DEVICE-RESIDENT (DESIGN.md §Engine hot path): every
step() issues exactly ONE jitted dispatch. Mixed iterations (prefill
chunks pending alongside live decode rows) fuse both advances into a
single ``M.mixed_step`` call instead of two back-to-back dispatches.
Decode-only iterations with ``decode_k > 1`` run K decode steps per
dispatch through a ``lax.scan`` micro-loop — argmax sampling,
EOS / budget / c_max termination, and the freeze-on-finish active
mask all on device; the slot state (last token, position, active,
remaining budget) stays resident on the device between dispatches and
the only host traffic is one batched (n_max, K) emitted-token sync.
Output tokens are BITWISE IDENTICAL to the K=1 sequential path on
every model family and both decode backends (test-pinned).

The KV cache comes in two layouts (DESIGN.md §Paged KV cache):

  * DENSE (default, bitwise-pinned): one contiguous ``(n_max, c_max)``
    row per slot — every slot pins worst-case KV for its lifetime.
  * PAGED (``paged=True``): one shared pool of fixed-size blocks plus
    a per-slot block table. A request only ever pins
    ceil((L_in + L_out_max)/block) blocks — ITS worst case, not the
    pool's — so at equal HBM the engine runs many more live slots
    (profiles.n_max_paged). A host-side free list allocates blocks on
    admit/chunk/decode; admission control refuses to place a request
    whose worst-case blocks the free list cannot cover, which makes
    mid-flight preemption unnecessary for correctness. Paged mode
    reproduces dense output tokens exactly on the same request stream.

OVERLOAD SURVIVAL (``preemption=True`` / ``max_queue_wait``;
DESIGN.md §Overload survival): when paged admission would defer, a
LIFO victim policy preempts the most recently admitted decoding slot,
swapping its blocks to a host-RAM tier (or discarding for recompute
when replay is cheaper); preempted requests re-enter the queue ahead
of new arrivals and resume bitwise-identically. A bounded queue sheds
new arrivals once the rolling queue-wait estimate exceeds
``max_queue_wait`` iterations, and a bounded out-of-order admission
window (``hol_window``) stops an oversized FIFO head from blocking
smaller requests that fit.

Paged mode can additionally run a REF-COUNTED PREFIX CACHE
(``prefix_cache=True``; DESIGN.md §Prefix caching): full prompt blocks
are content-addressed by a chained block hash, admission maps a new
request's matching leading blocks onto the physical blocks already
holding their KV (refcount increment, no allocation, no prefill), and
prefill resumes at the first cold token through the existing
``start_pos`` chunk path. Blocks whose refcount drops to zero stay
cached (evictable, LRU) so non-overlapping turns of the same session
still hit. Only FULL prompt blocks are ever shared; the final partial
block of a prompt is always private, so no shared block is ever
written after registration (copy-on-write degenerates to recompute of
at most ``block_size - 1`` suffix tokens).

Both jitted step functions DONATE the cache pytree (donate_argnums):
without donation XLA keeps the input and output cache alive across
every step — a 2x HBM tax on exactly the resource this engine
economizes. The CPU and TPU backends both honour it: a cache array
handed to a step is deleted, so host reads (cache_row, swap-out,
migration) always go through the current ``self.cache``.

The engine is MESH-AWARE (DESIGN.md §Sharded serving): pass ``mesh``
(usually one ``launch/mesh.make_submeshes`` replica submesh) and every
jitted step runs under jax.sharding — params sharded by the
Megatron-style ``distributed/sharding.py`` rules, the KV cache (dense
rows or the paged block pool) sharded over the model axis on the
kv-head dim (``serving_cache_specs``; sequence/block-dim fallback when
kv-heads don't divide), while the device-resident slot state
``(last_tok, pos, active, budget)`` and the block table REPLICATE
(slot scheduling is host-side bookkeeping; a sharded scheduler would
put admits on a collective path). The dirty-tracked re-uploads attach
the replicated NamedSharding; step outputs are pinned to the cache
shardings with with_sharding_constraint so donation reuses the sharded
buffers. Output tokens are pinned bitwise against the 1-device engine
(tests/test_decode_consistency.py, host-platform mesh). The Pallas
decode kernels are single-device programs — a sharded engine must be
built with ``decode_impl="xla"`` and refuses ``"pallas"``.

The engine is functional at the device boundary: all device state lives
in ``self.cache`` (a pytree) and is updated by jit'd steps. Slot and
block bookkeeping (which request occupies which slot, which physical
blocks it owns) is host-side — exactly the split a production
gateway/engine pair has.
"""
from __future__ import annotations

import dataclasses
import hashlib
import math
import time
from collections import Counter, OrderedDict
from functools import partial
from typing import Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.configs.base import ModelConfig
from repro.distributed import sharding as SH
from repro.models import layers as L
from repro.distributed.context import make_context
from repro.models import model as M
from repro.serving.config import ServingConfig
from repro.serving.draft import propose_draft
from repro.serving.tracing import Tracer


def prefill_buckets(c_chunk: int, min_bucket: int = 8) -> Tuple[int, ...]:
    """Padded chunk lengths: powers of two from ``min_bucket`` up to
    (and always including) ``c_chunk``. Every prefill call pads its
    longest pending chunk to the smallest bucket that fits, so the
    compiled-trace count is bounded by ``len(buckets)``."""
    buckets = []
    b = min(min_bucket, c_chunk)
    while b < c_chunk:
        buckets.append(b)
        b *= 2
    buckets.append(c_chunk)
    return tuple(buckets)


class EngineDead(RuntimeError):
    """The engine's device state is gone (injected crash / allocator
    exhaustion fault). Host-side bookkeeping (queue, emitted-token
    mirrors, host-offload KV tier) survives — reconfigure.salvage_states
    reads it to migrate every accepted request to a healthy pool."""


@dataclasses.dataclass
class ServeRequest:
    rid: int
    tokens: List[int]              # prompt token ids
    max_new_tokens: int
    category: str = "prose"
    # predicted output length (tokens), from the gateway's calibrated
    # L_out model. With ``lout_reservation`` on, paged admission
    # reserves ceil((L_in + hint)/block) blocks instead of the
    # max_new_tokens worst case; a request that outruns its hint
    # triggers a reservation-breach preemption (never an OOM). None =
    # worst-case reservation (the bitwise-default path).
    l_out_hint: Optional[int] = None


@dataclasses.dataclass
class ServeResult:
    rid: int
    output_tokens: List[int]
    prefill_iters: int
    decode_iters: int
    queue_iters: int               # iterations spent waiting for a slot
    shed: bool = False             # refused by stability-aware admission
    preemptions: int = 0           # times this request was preempted


@dataclasses.dataclass
class _PreemptedState:
    """Host checkpoint of a preempted slot (DESIGN.md §Overload
    survival). ``host_kv`` is the swap path's device->host copy of
    exactly the slot's block-table entries (paged: (L, n_blocks, bs,
    ...) per leaf; dense: the slot's cache row) — or None on the
    recompute path, where ``replay`` is re-prefilled instead:
    prompt + duplicated last prompt token + all-but-the-last generated
    token, i.e. exactly the token whose KV sat at positions
    0..pos-1 when the slot was preempted."""
    req: ServeRequest
    out: List[int]                 # tokens emitted before preemption
    pos: int                       # next KV position at preemption
    last_tok: int                  # token the next decode would feed
    replay: List[int]              # recompute-path prefill token list
    host_kv: object = None         # pytree of np arrays, or None
    n_blocks: int = 0              # device blocks held at preemption


class InferenceEngine:
    """One pool: n_max lockstep slots over a shared batched KV cache."""

    def __init__(self, cfg: ModelConfig, params, n_max: int, c_max: int,
                 c_chunk: Optional[int] = None, *,
                 config: Optional[ServingConfig] = None,
                 name: str = "engine", tracer: Optional[Tracer] = None,
                 **overrides):
        # -- ServingConfig shim (DESIGN.md §Serving API) -------------------
        # One validated config object replaces the legacy 16-kwarg
        # sprawl; explicit kwargs (including positional c_chunk) fold
        # into it via replace(), so kwargs-vs-config construction is
        # bitwise-identical (test-pinned). Unknown kwargs fail fast in
        # ServingConfig.replace with the valid option list.
        scfg = config if config is not None else ServingConfig()
        if c_chunk is not None:
            overrides = dict(overrides, c_chunk=c_chunk)
        if overrides:
            scfg = scfg.replace(**overrides)
        self.config = scfg
        c_chunk = scfg.c_chunk
        eos_id = scfg.eos_id
        decode_impl = scfg.decode_impl
        paged = scfg.paged
        block_size = scfg.block_size
        num_blocks = scfg.num_blocks
        prefix_cache = scfg.prefix_cache
        decode_k = scfg.decode_k
        spec_k = scfg.spec_k
        spec_ngram = scfg.spec_ngram
        mesh = scfg.mesh
        parallel = scfg.parallel
        preemption = scfg.preemption
        max_queue_wait = scfg.max_queue_wait
        swap_threshold = scfg.swap_threshold
        hol_window = scfg.hol_window
        if cfg.family not in ("dense", "moe", "vlm"):
            raise NotImplementedError(
                "engine supports attention-family models (the paper serves "
                "Llama-3-70B); SSM decode runs through models.decode_step")
        if prefix_cache and not paged:
            raise ValueError("prefix_cache=True needs the paged KV cache "
                             "(block granularity is what gets shared)")
        if spec_k > 1 and cfg.attention_window:
            # a rejected draft's KV write at ring slot (p+i) % window
            # would clobber LIVE in-window history the retried position
            # still attends (layers.write_chunk_kv overwrite contract
            # only holds for full-attention offsets)
            raise NotImplementedError(
                "speculative decoding needs full-attention KV offsets; "
                "windowed ring-buffer caches alias live history under "
                "rejected drafts")
        # -- mesh / tensor parallel (DESIGN.md §Sharded serving) -----------
        self.mesh = mesh
        self.parallel = (parallel or make_context(mesh)) \
            if mesh is not None else None
        self.tp_degree = int(mesh.shape[self.parallel.model_axis]) \
            if mesh is not None else 1
        if mesh is not None and decode_impl == "pallas":
            # The Pallas decode kernels are single-device programs;
            # driving them over a mesh-sharded cache needs a shard_map
            # integration that does not exist yet. A sharded engine
            # must be asked for the XLA path (decode_impl="xla").
            raise NotImplementedError(
                "decode_impl='pallas' runs on one device only; a "
                "mesh-sharded engine needs decode_impl='xla'")
        self.decode_impl = decode_impl
        if mesh is not None:
            # replicated NamedSharding for scheduler-state uploads
            self._replicated = NamedSharding(mesh, PartitionSpec())
            pspecs = SH.param_specs(params, self.parallel)
            params = jax.device_put(params, SH.to_named(pspecs, mesh))
        else:
            self._replicated = None
        self.cfg = cfg
        self.params = params
        # the pool's name: it labels this engine's spans and names its
        # step programs (``<name>_mixed`` etc.) in a profiler trace
        self.name = name
        self.tracer = tracer if tracer is not None else Tracer()
        self.n_max = n_max
        self.c_max = c_max
        self.c_chunk = min(c_chunk, c_max)
        self.buckets = prefill_buckets(self.c_chunk)
        self.eos_id = eos_id
        self.paged = paged
        self.prefix_cache = prefix_cache
        if paged:
            self.block_size = block_size
            # logical blocks per slot: enough to address c_max tokens
            self.blocks_per_slot = math.ceil(c_max / block_size)
            # default pool: equal HBM with the dense layout (n_max
            # worst-case rows); callers exploiting paging pass a larger
            # n_max at the same num_blocks (profiles.n_max_paged).
            self.num_blocks = (num_blocks if num_blocks is not None
                               else n_max * self.blocks_per_slot)
            self._cache_shardings = self._serving_shardings(
                lambda: M.init_paged_cache(cfg, self.num_blocks,
                                           block_size), paged=True)
            self.cache = M.init_paged_cache(cfg, self.num_blocks,
                                            block_size,
                                            shardings=self._cache_shardings)
            # host-side allocator state (free list + per-slot tables)
            self._free: List[int] = list(range(self.num_blocks))
            self._reserved = 0          # worst-case blocks not yet alloc'd
            self.block_tables = np.zeros((n_max, self.blocks_per_slot),
                                         np.int32)
            self._slot_blocks: List[List[int]] = [[] for _ in range(n_max)]
            # outstanding (not-yet-allocated) worst-case reservation per
            # slot; decremented as _ensure_blocks turns it into blocks
            self._slot_reserved = [0] * n_max
            # -- ref-counted prefix cache (DESIGN.md §Prefix caching) --
            # _ref[phys]: live slot-table references to a physical block
            self._ref = np.zeros(self.num_blocks, np.int64)
            # chained block hash -> physical block holding its KV
            self._prefix_map: Dict[bytes, int] = {}
            # physical block -> its registered hash (reverse index)
            self._block_hash: Dict[int, bytes] = {}
            # ref == 0 blocks still holding cached prefixes, LRU order;
            # they are allocatable (evicted) only when _free runs dry
            self._cached_free: OrderedDict = OrderedDict()
            # per-slot chain hashes of its FULL prompt blocks, and how
            # many leading blocks are already in the prefix map
            self._slot_hashes: List[List[bytes]] = [[] for _ in range(n_max)]
            self._slot_registered = [0] * n_max
            self._hash_seed = hashlib.sha1(
                f"{cfg.name}/{block_size}".encode()).digest()
            self.prefix_stats = {"lookups": 0, "hit_blocks": 0,
                                 "hit_tokens": 0, "allocated_blocks": 0,
                                 "registered_blocks": 0, "evicted_blocks": 0}
            # device copy of the block table, refreshed only when the
            # allocator touches it (steady-state decode crosses a block
            # boundary once per block_size tokens — re-uploading every
            # step would put a host->device copy on the hot path)
            self._bt_device = None
        else:
            self._cache_shardings = self._serving_shardings(
                lambda: M.init_cache(cfg, n_max, c_max), paged=False)
            self.cache = M.init_cache(cfg, n_max, c_max,
                                      shardings=self._cache_shardings)
        # chain hashes memoized for WAITING requests (keyed by rid;
        # dropped on admit/refuse) — the FIFO head re-probes every
        # iteration while blocked and must not rehash its prompt.
        # Always present (the admit/refuse cleanup paths are shared
        # between dense and paged modes); only ever filled when the
        # prefix cache is on.
        self._req_hashes: Dict[int, List[bytes]] = {}
        # per-slot host state
        self.slot_req: List[Optional[ServeRequest]] = [None] * n_max
        self.slot_pos = np.zeros(n_max, np.int32)        # next position
        self.slot_prefill_left: List[List[int]] = [[] for _ in range(n_max)]
        self.slot_out: List[List[int]] = [[] for _ in range(n_max)]
        self.slot_last_tok = np.zeros(n_max, np.int32)
        self.waiting: List[ServeRequest] = []
        self.results: Dict[int, ServeResult] = {}
        self.iteration = 0
        self._queue_iters: Dict[int, int] = {}
        # rid -> (iteration, perf_counter_ns) when it (re-)entered the
        # queue
        self._enqueued_at: Dict[int, Tuple[int, int]] = {}
        # wall-clock queue wait, summed over admissions into a slot
        # (/metrics, the benchmark's sched.queue_wait_ms_mean)
        self.queue_wait_ns = 0
        self.queue_admissions = 0
        # head-of-queue admissions deferred because the block pool could
        # not cover the request's worst case
        self.admissions_deferred = 0
        # paged KV, summed over dispatching steps: blocks reserved
        # (allocated + worst case not yet allocated) and blocks holding
        # written tokens
        self.kv_reserved_block_steps = 0
        self.kv_used_block_steps = 0
        self._prefill_iters: Dict[int, int] = {}
        # -- overload survival (DESIGN.md §Overload survival) --------------
        # preemption: when paged admission would defer, preempt the most
        # recently admitted decoding slot (LIFO; ties by largest
        # remaining worst-case reservation), moving its KV to a host-RAM
        # tier (swap) or discarding it for replay (recompute).
        self.preemption = bool(preemption)
        # bounded queue: estimated queue wait (iterations) above which
        # submit() REFUSES (sheds) instead of deferring; None = unbounded
        self.max_queue_wait = max_queue_wait
        # swap-vs-recompute knee, in COLD-SUFFIX tokens (tokens whose KV
        # replay would actually recompute, net of live prefix-cache
        # hits): swap iff cold > threshold. 0 (default) always swaps —
        # the bitwise-safe choice; callers derive a throughput-based
        # threshold from HardwareProfile.recompute_threshold_tokens().
        self.swap_threshold = 0 if swap_threshold is None \
            else int(swap_threshold)
        # bounded out-of-order admission window for a deferring FIFO
        # head (HOL fix), with a per-head bypass cap as starvation guard
        self.hol_window = max(0, int(hol_window))
        self.hol_max_bypass = 8 * max(1, self.hol_window)
        self._preempted: Dict[int, _PreemptedState] = {}
        # resumed recompute replays end on the duplicated/previous
        # token; the true next fed token is overridden at prefill end
        self._resume_last_tok: Dict[int, int] = {}
        self._rid_preemptions: Dict[int, int] = {}
        self._hol_bypassed: Dict[int, int] = {}   # head rid -> bypasses
        self._slot_admit_iter = [0] * n_max       # LIFO victim key
        self.overload_stats = {"preempted": 0, "swapped_out": 0,
                               "swapped_in": 0, "recomputed": 0,
                               "swapped_blocks": 0, "shed": 0,
                               "hol_bypass": 0, "reservation_breach": 0}
        # -- fault injection (DESIGN.md §Live re-provisioning) -------------
        # None = healthy. "killed"/"oom" make the next device touch
        # raise EngineDead; "wedged" makes step() return without
        # advancing the iteration clock (a stall the gateway's health
        # policy detects). Set only by reconfigure.FaultInjector.
        self._fault: Optional[str] = None
        # -- output-length-aware reservation (DESIGN.md §Serving API) ------
        # opt-in: paged admission reserves the request's PREDICTED
        # footprint (l_out_hint) instead of its max_new_tokens worst
        # case, multiplying admission capacity when callers over-claim
        # max_tokens; preemption is the safety net when a prediction
        # runs short (see _reservation_breach)
        self.lout_reservation = bool(scfg.lout_reservation)
        # rolling service-rate estimate (EMA per iteration) for the
        # stability-aware admission bound (Little's-law style)
        self._completed_total = 0
        self._mu_hat = 0.0            # completions / iteration
        self._rate_alpha = 0.05
        # buckets that actually compiled a prefill trace this lifetime
        self.prefill_buckets_used: Set[int] = set()
        # -- hot-path accounting (DESIGN.md §Engine hot path) --
        # one DISPATCH == one jitted call; one ITERATION == one
        # lockstep model step. With decode_k > 1 a single decode
        # dispatch advances decode_k iterations, so the two clocks
        # diverge — queue/TTFT accounting stays in iterations.
        self.decode_k = max(1, int(decode_k))
        # -- self-speculative decoding (DESIGN.md §Speculative decoding)
        # spec_k = verify-window width W: 1 carried token + up to W-1
        # host-proposed draft tokens per decode micro-iteration. The
        # host proposes ONE draft continuation of up to
        # decode_k * (spec_k - 1) tokens per slot per dispatch; the
        # scan walks it with a per-row cursor, so drafting composes
        # with the K-step scan without any mid-scan host sync.
        self.spec_k = max(1, int(spec_k))
        self.spec_ngram = max(1, int(spec_ngram))
        self.spec_stats = {
            "drafted_tokens": 0,     # host proposer output, pre-clip
            "proposed_tokens": 0,    # draft tokens fed to verify windows
            "accepted_tokens": 0,    # fed drafts the model confirmed
            "verify_windows": 0,     # live verify micro-iterations
        }
        self.dispatches = 0            # jitted calls, total
        self.decode_dispatches = 0     # decode-only scan/step calls
        self.decode_tokens_emitted = 0  # tokens emitted, ANY dispatch kind
        # tokens emitted by decode-ONLY dispatches (the amortization
        # metric's denominator must match its numerator's scope: a
        # fused mixed dispatch also emits decode tokens but is not a
        # decode-only call, so counting its tokens here would let the
        # <= 1/K gate pass vacuously on mixed-heavy traffic)
        self._decode_only_tokens = 0
        self._occ_slot_iters = 0       # occupied slot-iterations
        # -- model counters (always on; /metrics) --
        # token rows through the held expert layers, once per MoE layer
        # (prefill chunk tokens and decode rows; a chunk's padding and
        # idle slots are routed nowhere), and the KV pages the paged
        # decode kernel copies, by attention kind: a windowed layer's
        # sweep starts at the page of its first key in the window
        self.moe_rows_total = 0
        self.attn_pages_swept = {"window": 0, "full": 0}
        windows = cfg.layer_windows or (0,) * cfg.num_layers
        self._moe_layers = (cfg.num_layers - cfg.dense_layers
                            if cfg.moe is not None else 0)
        self._windows = [w for w in windows if w]
        self._full_layers = len(windows) - len(self._windows)
        self._kernel_pages = paged and decode_impl == "pallas" \
            and self.spec_k == 1
        # -- device-resident decode state (decode_k > 1 scan path) --
        # (last_tok, pos, active, budget) live on device BETWEEN scan
        # dispatches; host mirrors (slot_last_tok / slot_pos / slot_out
        # lengths) are updated from the batched emitted-token sync, so
        # steady-state decode uploads NOTHING. Any host-side slot write
        # outside that replay (admit, prefill advance, mixed step)
        # marks the device copy dirty — same snapshot-on-upload
        # discipline as _bt_device.
        self._dev_state = None
        self._dev_dirty = True
        # donate_argnums=1: the cache pytree is consumed by each step
        # and its buffer reused for the output (no 2x HBM residency)
        if paged:
            self._decode = self._program(
                "decode", partial(self._paged_decode_fn, decode_impl), 1)
            self._prefill_step = self._program(
                "prefill", self._paged_prefill_fn, 1)
            # decode scan: cache + carried device state donated; the
            # block table (arg 3) is the cached _bt_device and must
            # survive the call. spec_k > 1 swaps the per-token scan
            # body for the speculative verify body — same carry, same
            # donation (the draft table args are fresh per dispatch
            # and not donated).
            if self.spec_k > 1:
                self._decode_scan = self._program(
                    "decode_scan",
                    partial(self._paged_spec_scan_fn, decode_impl,
                            self.decode_k, self.spec_k), (1, 2, 4, 5, 6))
            else:
                self._decode_scan = self._program(
                    "decode_scan",
                    partial(self._paged_decode_scan_fn, decode_impl,
                            self.decode_k), (1, 2, 4, 5, 6))
            self._mixed = self._program(
                "mixed", partial(self._paged_mixed_fn, decode_impl), 1)
        else:
            self._decode = self._program(
                "decode", partial(self._decode_fn, decode_impl), 1)
            # NOT static in chunk length: the bucketed token array's shape
            # selects the trace, so traces are bounded by len(self.buckets)
            self._prefill_step = self._program(
                "prefill", partial(self._prefill_fn, decode_impl), 1)
            if self.spec_k > 1:
                self._decode_scan = self._program(
                    "decode_scan",
                    partial(self._spec_scan_fn, decode_impl, self.decode_k,
                            self.spec_k), (1, 2, 3, 4, 5))
            else:
                self._decode_scan = self._program(
                    "decode_scan",
                    partial(self._decode_scan_fn, decode_impl,
                            self.decode_k), (1, 2, 3, 4, 5))
            self._mixed = self._program(
                "mixed", partial(self._mixed_fn, decode_impl), 1)

    def _program(self, program: str, fn, donate):
        """``fn`` jitted under a stable per-pool name: its XLA module
        reads ``jit_<pool>_<program>`` (a profiler trace's "XLA
        Modules" line) and its ops sit under the named scope
        ``<pool>.<program>``."""
        scope = f"{self.name}.{program}"

        def step_program(*args):
            with jax.named_scope(scope):
                return fn(*args)
        step_program.__name__ = step_program.__qualname__ = \
            f"{self.name}_{program}"
        return jax.jit(step_program, donate_argnums=donate)

    # -- mesh sharding (DESIGN.md §Sharded serving) ------------------------
    def _serving_shardings(self, abstract_init, paged: bool):
        """NamedSharding pytree for the engine cache (None on a
        1-device engine): kv-head dim over the model axis, guarded
        seq/block fallback — specs from serving_cache_specs over the
        abstract (eval_shape) cache structure, so no cache is ever
        materialized just to learn its shapes."""
        if self.mesh is None:
            return None
        struct = jax.eval_shape(abstract_init)
        specs = SH.serving_cache_specs(struct, self.parallel, paged=paged)
        return SH.to_named(specs, self.mesh)

    def _constrain_cache(self, cache):
        """Pin a step-OUTPUT cache to the engine's shardings inside
        jit. Scatters/dynamic_update_slice leave GSPMD free to
        re-propagate layouts per trace; the constraint keeps every
        output bitwise-stably sharded like its (donated) input, so the
        donation reuses the sharded buffers."""
        if self._cache_shardings is None:
            return cache
        return jax.tree.map(jax.lax.with_sharding_constraint, cache,
                            self._cache_shardings)

    def _upload(self, host_array):
        """Host->device upload of scheduler state (tokens, positions,
        masks, budgets, block tables): REPLICATED across the mesh when
        sharded — slot state is host-scheduled and every device needs
        the full view. Callers pass snapshots (np.array copies; the
        async-aliasing rule from PR 1 applies unchanged)."""
        if self._replicated is not None:
            return jax.device_put(np.asarray(host_array), self._replicated)
        return jnp.asarray(host_array)

    def devices(self) -> List:
        """Devices this engine replica occupies (placement printing /
        fleet accounting); a 1-device engine reports the default
        device."""
        if self.mesh is not None:
            return list(self.mesh.devices.flat)
        return [jax.devices()[0]]

    def cache_bytes_per_device(self) -> int:
        """Max KV-cache bytes resident on any ONE device — the
        per-device HBM figure profiles.devices_per_replica models
        (~1/tp of the total under the kv-head sharding)."""
        per_dev: Dict[int, int] = {}
        for leaf in jax.tree.leaves(self.cache):
            shards = getattr(leaf, "addressable_shards", None)
            if shards:
                for s in shards:
                    per_dev[s.device.id] = \
                        per_dev.get(s.device.id, 0) + s.data.nbytes
            else:
                per_dev[-1] = per_dev.get(-1, 0) + leaf.nbytes
        return max(per_dev.values(), default=0)

    # ------------------------------------------------------------------ API
    def submit(self, req: ServeRequest) -> bool:
        """Enqueue a request. Stability-aware admission (DESIGN.md
        §Overload survival): with ``max_queue_wait`` set, a request
        whose estimated queue wait already exceeds the deadline is
        REFUSED up front (shed) rather than deferred — bounding the
        queue is what keeps P99 TTFT degrading gracefully instead of
        collapsing past the stability boundary. Returns False iff
        shed (the empty result carries ``shed=True``)."""
        if (self.max_queue_wait is not None and self.waiting
                and self._completed_total > 0
                and self.queue_wait_estimate() > self.max_queue_wait):
            self.results[req.rid] = ServeResult(req.rid, [], 0, 0, 0,
                                                shed=True)
            self.overload_stats["shed"] += 1
            return False
        self.waiting.append(req)
        self._mark_queued(req.rid)
        return True

    def _mark_queued(self, rid: int) -> None:
        """``rid`` (re-)enters the queue now."""
        self._enqueued_at[rid] = (self.iteration, time.perf_counter_ns())

    def _mark_admitted(self, rid: int) -> None:
        """``rid`` left the queue for a slot: its wait, in iterations
        (per request) and in wall time (summed per engine)."""
        it, ns = self._enqueued_at.pop(rid)
        self._queue_iters[rid] = self._queue_iters.get(rid, 0) \
            + self.iteration - it
        self.queue_wait_ns += time.perf_counter_ns() - ns
        self.queue_admissions += 1

    def busy(self) -> bool:
        return any(r is not None for r in self.slot_req) or bool(self.waiting)

    def queue_wait_estimate(self) -> float:
        """Estimated ITERATIONS a request submitted now would wait for
        a slot: queue depth / rolling service-rate estimate
        (completions per iteration). The EMA tracks recent throughput
        but starts at 0 and needs ~1/alpha iterations to warm up, so it
        is floored by the CUMULATIVE completion rate — otherwise the
        first completions make the estimate diverge and shed a burst of
        perfectly servable early arrivals. Under a real stall (both
        rates -> 0 with requests queued) the estimate still diverges —
        exactly when shedding should kick in. 0.0 before any completion
        (no evidence yet)."""
        if self._completed_total == 0:
            return 0.0
        mu = max(self._mu_hat,
                 self._completed_total / max(1, self.iteration))
        if mu <= 0.0:
            return float("inf")
        return len(self.waiting) / mu

    def host_tier_blocks(self) -> int:
        """Device blocks' worth of KV currently parked in the host
        swap tier (0 for recompute-path preemptions and dense rows)."""
        return sum(st.n_blocks for st in self._preempted.values())

    def _update_rate_estimates(self, k_iters: int, completions: int) -> None:
        """Fold one dispatch's worth of iterations into the EMA rate
        estimate. A decode_k scan advances k iterations per call, so
        the decay compounds per ITERATION, keeping the estimate
        comparable across K."""
        if k_iters <= 0:
            return
        decay = (1.0 - self._rate_alpha) ** k_iters
        self._mu_hat = decay * self._mu_hat \
            + (1.0 - decay) * (completions / k_iters)

    def utilization_snapshot(self, detail: bool = False):
        """Mean PER-ITERATION slot occupancy since engine start.

        With decode_k > 1 a slot that finishes mid-scan is idle for the
        remaining micro-iterations of that dispatch even though the
        host still shows it occupied until the batched sync — so
        occupancy is accumulated per iteration (a finishing slot
        contributes exactly the iterations it actually decoded), not
        per dispatch. This is the occupancy the DES's rho_hat estimator
        measures, which keeps analytic-vs-engine validation comparable
        at any K. Before the first iteration, falls back to the
        instantaneous occupied fraction.

        ``detail=True`` returns a dict instead: the occupancy plus the
        overload-survival counters (shed / preempt / swap / HOL
        bypass), queue depth, host-tier blocks and the rolling
        queue-wait estimate — the operator's overload dashboard."""
        if self.iteration == 0:
            occ = sum(r is not None for r in self.slot_req) / self.n_max
        else:
            occ = self._occ_slot_iters / (self.n_max * self.iteration)
        if not detail:
            return occ
        return {"occupancy": occ,
                "queue_depth": len(self.waiting),
                "queue_wait_est_iters": self.queue_wait_estimate(),
                "service_rate_per_iter": self._mu_hat,
                "host_tier_blocks": self.host_tier_blocks(),
                **self.overload_stats}

    def dispatches_per_token(self) -> float:
        """Decode-only jitted calls per token THEY emitted — the host
        round-trip overhead metric the multi-step scan amortizes
        (1/decode_k in steady-state decode). Tokens emitted by fused
        mixed dispatches are excluded from both sides."""
        if self._decode_only_tokens == 0:
            return float("inf")
        return self.decode_dispatches / self._decode_only_tokens

    def free_block_count(self) -> int:
        """Allocatable physical blocks (paged mode): the free list plus
        the cached-but-unreferenced tier (evictable prefix blocks) —
        the same quantity admission control reserves against."""
        return self._available_blocks() if self.paged else 0

    def prefix_cache_blocks(self) -> int:
        """Physical blocks currently content-addressable by prefix hash
        (referenced or evictable)."""
        return len(self._prefix_map) if self.paged else 0

    def kv_tokens_held(self) -> int:
        """Tokens of KV memory currently pinned: paged counts DISTINCT
        referenced physical blocks (a prefix block shared by many slots
        pins HBM once; evictable cached blocks are reclaimable, not
        pinned); dense pins c_max per occupied slot."""
        if self.paged:
            held = self.num_blocks - len(self._free) - len(self._cached_free)
            return held * self.block_size
        return sum(r is not None for r in self.slot_req) * self.c_max

    def run_to_completion(self, max_iters: int = 100_000) -> Dict[int, ServeResult]:
        while self.busy() and self.iteration < max_iters:
            self.step()
        return self.results

    def num_compiled_traces(self) -> Dict[str, int]:
        """Compiled-trace counts for the jitted step functions.
        The fixed-shape guarantee, whatever the request-length mix:
        decode <= 1, decode_scan <= 1 (its K is baked in at
        construction), and prefill/mixed <= len(self.buckets) each
        (the bucketed chunk shape selects the trace)."""
        return {
            "decode": self._decode._cache_size(),
            "decode_scan": self._decode_scan._cache_size(),
            "prefill": self._prefill_step._cache_size(),
            "mixed": self._mixed._cache_size(),
        }

    def cache_row(self, s: int):
        """Host copy of slot ``s``'s cache row (testing / debugging).
        In paged mode the row is materialized through the block table
        (unallocated logical blocks read physical block 0 — garbage
        beyond the slot's length, exactly like a dense row)."""
        if self.paged:
            idx = np.array(self.block_tables[s], np.int32)

            def gather(a):
                arr = np.asarray(a)          # (L, P, bs, Hkv, hd)
                out = arr[:, idx]            # (L, NB, bs, Hkv, hd)
                return out.reshape(arr.shape[0], -1, *arr.shape[3:])
            return jax.tree.map(gather, self.cache)
        return jax.tree.map(
            lambda a: np.asarray(
                jax.lax.index_in_dim(a, s, self._batch_axis(a),
                                     keepdims=False)), self.cache)

    # ----------------------------------------------------------------- step
    def step(self) -> None:
        """One lockstep step: admit, then ONE jitted dispatch
        (DESIGN.md §Engine hot path):

          * prefill chunks pending AND decode rows live -> one fused
            M.mixed_step call advances both (previously two
            back-to-back dispatches);
          * only prefill chunks -> one batched prefill call;
          * only decode rows -> one decode dispatch advancing
            ``decode_k`` iterations via the on-device scan (K = 1 runs
            the legacy single-step path, bitwise-pinned).

        The iteration clock advances by the number of model iterations
        the dispatch performed (decode_k for a scan), never by
        dispatches. With the tracer on, the step is the span
        ``eng.step`` (its kind: idle / prefill / mixed / decode), with
        ``eng.admit``, ``eng.alloc``, ``eng.dispatch``, ``eng.sync``
        and ``eng.replay`` inside."""
        with self.tracer.span("eng.step", pool=self.name) as sp:
            kind = self._step()
            if sp is not None:
                sp.kind = kind

    def _step(self) -> str:
        if self._fault == "killed":
            raise EngineDead(f"engine fault injected: {self._fault}")
        if self._fault == "wedged":
            # a wedged step consumes wall time but never advances the
            # iteration clock — exactly the signature HealthPolicy keys
            # on (busy engine, frozen iteration counter)
            return "idle"
        it0, done0 = self.iteration, self._completed_total
        self.iteration += 1
        with self.tracer.span("eng.admit", pool=self.name):
            self._admit()
        chunks: Dict[int, List[int]] = {}
        for s in range(self.n_max):
            req = self.slot_req[s]
            if req is None or not self.slot_prefill_left[s]:
                continue
            chunks[s] = self.slot_prefill_left[s][: self.c_chunk]
            self.slot_prefill_left[s] = self.slot_prefill_left[s][self.c_chunk:]
        decode_mask = np.array(
            [self.slot_req[s] is not None and s not in chunks
             and not self.slot_prefill_left[s] for s in range(self.n_max)],
            bool)
        occupied = sum(r is not None for r in self.slot_req)
        if self.paged:
            with self.tracer.span("eng.alloc", pool=self.name):
                decode_mask = self._provision_blocks(chunks, decode_mask)
        if chunks and decode_mask.any():
            self._occ_slot_iters += occupied
            self._run_mixed(chunks, decode_mask)
            kind = "mixed"
        elif chunks:
            self._occ_slot_iters += occupied
            self._run_prefill_chunks(chunks)
            kind = "prefill"
        elif decode_mask.any():
            if self.spec_k > 1:
                self._run_spec_scan(decode_mask)
            elif self.decode_k > 1:
                self._run_decode_scan(decode_mask)
            else:
                self._occ_slot_iters += occupied
                self._run_decode(decode_mask)
            kind = "decode"
        else:
            self._occ_slot_iters += occupied
            kind = "idle"
        self._update_rate_estimates(self.iteration - it0,
                                    self._completed_total - done0)
        if self.paged and kind != "idle":
            self._count_kv_blocks()
        return kind

    def _provision_blocks(self, chunks: Dict[int, List[int]],
                          decode_mask: np.ndarray) -> np.ndarray:
        """Allocate the blocks this step's dispatch will write: each
        pending chunk, and up to one dispatch's tokens per decode row.
        Returns the decode mask, narrowed when a reservation breach
        preempted rows."""
        for s, chunk in chunks.items():
            ok = self._ensure_blocks(s,
                                     int(self.slot_pos[s]) + len(chunk))
            assert ok, "prefill outran its reservation (the prompt " \
                "is always fully covered, hint or not)"
        if decode_mask.any():
            # max tokens one decode-only dispatch can emit per row:
            # decode_k micro-iterations x up to spec_k tokens each
            # (the verify body clips each window to budget, so the
            # per-slot advance never exceeds its admission-time
            # worst-case reservation). Pre-provisioning here is
            # what keeps the scan from ever re-entering the host
            # allocator mid-dispatch.
            k = self.decode_k * self.spec_k if not chunks else 1
            breached = False
            for s in np.where(decode_mask)[0]:
                req = self.slot_req[int(s)]
                if req is None:     # preempted by an earlier breach
                    continue
                left = req.max_new_tokens - len(self.slot_out[int(s)])
                needed = int(self.slot_pos[s]) + min(k, left)
                if not self._ensure_blocks(int(s), needed):
                    # tightened (l_out_hint) reservation outrun:
                    # free blocks by preemption — possibly of this
                    # very slot — and keep the dispatch going
                    self._reservation_breach(int(s), needed,
                                             protected=chunks.keys())
                    breached = True
            if breached:
                # breach preemptions may have emptied slots the
                # mask was computed over (victims, or the breacher
                # itself) — only still-occupied rows decode; an
                # all-False mask falls through to the idle branch
                decode_mask &= np.array(
                    [r is not None for r in self.slot_req], bool)
        return decode_mask

    def _count_kv_blocks(self) -> None:
        """Add this step's paged KV to the block counters: blocks
        reserved (referenced + worst case not yet allocated, the
        allocator's own accounting) and blocks holding written tokens
        (each occupied slot's ceil(position / block_size))."""
        self.kv_reserved_block_steps += \
            self.num_blocks - self._available_blocks() + self._reserved
        bs = self.block_size
        self.kv_used_block_steps += sum(
            -(-int(self.slot_pos[s]) // bs) for s in range(self.n_max)
            if self.slot_req[s] is not None)

    # ------------------------------------------------------------ internals
    def _worst_case_blocks(self, req: ServeRequest) -> int:
        return math.ceil((len(req.tokens) + req.max_new_tokens)
                         / self.block_size)

    # -- prefix cache (DESIGN.md §Prefix caching) --------------------------
    def _chain_hashes(self, tokens: List[int]) -> List[bytes]:
        """One chained content hash per FULL prompt block: h_i =
        H(h_{i-1} || tokens[i*bs:(i+1)*bs]), seeded per (model, block
        size). Chaining makes a block hash identify the whole prefix up
        to and including the block, so equal hashes => equal KV content
        (prefill K/V at position p is a pure function of the prefix)."""
        bs = self.block_size
        out, h = [], self._hash_seed
        for i in range(len(tokens) // bs):
            blk = np.asarray(tokens[i * bs:(i + 1) * bs], np.int64)
            h = hashlib.sha1(h + blk.tobytes()).digest()
            out.append(h)
        return out

    def _prefix_hits(self, hashes: List[bytes]) -> int:
        """Longest chain of leading full blocks already cached."""
        n = 0
        for h in hashes:
            if h not in self._prefix_map:
                break
            n += 1
        return n

    def _available_blocks(self) -> int:
        """Blocks an allocation could obtain: free + evictable."""
        return len(self._free) + len(self._cached_free)

    def _alloc_block(self) -> int:
        """Pop a free block; when the free list is dry, evict the
        least-recently-released cached prefix block (its hash leaves
        the prefix map — the content is about to be overwritten)."""
        if self._fault == "oom":
            # injected allocator exhaustion: a real fleet hits this when
            # HBM is lost (ECC fault, partial device loss). Raised from
            # INSIDE the allocator, so counters the caller already
            # decremented stay inconsistent — salvage reads host mirrors
            # only and never trusts this engine's allocator again.
            raise EngineDead("engine fault injected: allocator exhausted")
        if self._free:
            return self._free.pop()
        phys, _ = self._cached_free.popitem(last=False)
        h = self._block_hash.pop(phys)
        del self._prefix_map[h]
        self.prefix_stats["evicted_blocks"] += 1
        return phys

    def _register_prefix_blocks(self, s: int) -> None:
        """Publish slot ``s``'s full prompt blocks whose KV the prefill
        has now completely written (slot_pos advanced past their end).
        First writer wins: if another slot registered the same chain
        hash concurrently, this slot's copy stays private."""
        hashes = self._slot_hashes[s]
        done = min(len(hashes), int(self.slot_pos[s]) // self.block_size)
        blocks = self._slot_blocks[s]
        for i in range(self._slot_registered[s], done):
            h = hashes[i]
            if h not in self._prefix_map:
                phys = blocks[i]
                self._prefix_map[h] = phys
                self._block_hash[phys] = h
                self.prefix_stats["registered_blocks"] += 1
        self._slot_registered[s] = done

    def _refuse(self, req: ServeRequest, qi: int = 0) -> None:
        """Refuse waiting[qi]: empty result, no leaked host entries."""
        self.waiting.pop(qi)
        self.results[req.rid] = ServeResult(req.rid, [], 0, 0, 0)
        self._enqueued_at.pop(req.rid, None)
        self._queue_iters.pop(req.rid, None)
        self._req_hashes.pop(req.rid, None)
        self._hol_bypassed.pop(req.rid, None)
        self._resume_last_tok.pop(req.rid, None)
        self._preempted.pop(req.rid, None)
        self._rid_preemptions.pop(req.rid, None)

    def _admit(self) -> None:
        for s in range(self.n_max):
            if self.slot_req[s] is not None:
                continue
            while self.waiting:
                st = self._try_admit(s, 0, consume=True)
                if st == "refused":
                    # the slot's admit chance is not consumed: the next
                    # waiting request gets it this same iteration
                    continue
                if st == "admitted":
                    break
                # The FIFO head DEFERS: the allocatable blocks cannot
                # cover its worst case (DESIGN.md §Paged KV cache).
                self.admissions_deferred += 1
                # Escalations, in order:
                # 1) preemption (opt-in): free blocks by preempting the
                #    most recently admitted decoding slot. A RESUMED
                #    head never triggers preemption — a swap-in that
                #    preempted its preemptor would ping-pong forever.
                if self.preemption \
                        and self.waiting[0].rid not in self._preempted:
                    victim = self._select_victim()
                    if victim is not None:
                        self.preempt_slot(victim, requeue_index=1)
                        continue       # retry the head on freed blocks
                # 2) bounded out-of-order admission (HOL fix): a small
                #    queued request may take the slot, starvation-capped
                if self._try_hol_bypass(s):
                    break
                # 3) stay queued until completions return blocks
                return

    def _try_admit(self, s: int, qi: int, consume: bool) -> str:
        """Try to place ``waiting[qi]`` into the free slot ``s``.

        Returns "admitted", "refused" (popped with an empty result —
        only when ``consume``), "defer" (fits the engine but not the
        block pool right now), or "skip" (would be refused, but this is
        a HOL bypass probe which must not consume the request)."""
        req = self.waiting[qi]
        state = self._preempted.get(req.rid)
        if len(req.tokens) + req.max_new_tokens > self.c_max:
            # gateway guarantees this never happens (Eq. 15); a
            # direct-submitted oversized request is refused without
            # leaking host entries
            if not consume:
                return "skip"
            self._refuse(req, qi)
            return "refused"
        if state is not None and state.host_kv is not None:
            return self._swap_in(s, qi, state)
        # fresh admission — or a preempted request REPLAYING through
        # prefill (recompute path): identical block arithmetic over the
        # replay token list, which reconstructs cache positions
        # 0..pos-1 exactly (see _PreemptedState)
        tokens_full = req.tokens if state is None else state.replay
        budget_left = req.max_new_tokens \
            - (0 if state is None else len(state.out))
        hits = 0
        if self.paged:
            worst = math.ceil((len(tokens_full) + budget_left)
                              / self.block_size)
            if worst > self.num_blocks:
                # can NEVER be covered (pool smaller than the request's
                # worst case): refuse like oversized, or the FIFO head
                # would defer forever
                if not consume:
                    return "skip"
                self._refuse(req, qi)
                return "refused"
            if self.prefix_cache:
                # memoized per rid: a blocked FIFO head probes every
                # iteration and must not rehash its whole prompt each
                # time (host hot path)
                if req.rid not in self._req_hashes:
                    self._req_hashes[req.rid] = \
                        self._chain_hashes(tokens_full)
                hashes = self._req_hashes[req.rid]
            else:
                hashes = []
            hits = self._prefix_hits(hashes)
            # output-length-aware reservation (lout_reservation): a
            # FRESH admission reserves its predicted footprint
            # (l_out_hint, floored at one decode token) instead of the
            # max_new_tokens worst case — the oversized/never-coverable
            # refusals above stay on the true worst case. Resumed
            # preemptees always reserve the full worst case: a request
            # that already breached once must not ping-pong.
            plan = worst
            if (self.lout_reservation and state is None
                    and req.l_out_hint is not None):
                reserve_budget = min(budget_left,
                                     max(1, int(req.l_out_hint)))
                plan = math.ceil((len(tokens_full) + reserve_budget)
                                 / self.block_size)
            # cached leading blocks are reused, not allocated: only the
            # cold suffix needs worst-case coverage. BUT pinning an
            # EVICTABLE hit (ref 0, cached-free) removes it from the
            # allocatable tiers without adding to _reserved, so it must
            # be charged here too or earlier slots' outstanding
            # reservations get over-committed and the allocator runs dry.
            need = max(0, plan - hits)
            evictable_hits = sum(
                1 for i in range(hits)
                if self._ref[self._prefix_map[hashes[i]]] == 0)
            if need + evictable_hits > \
                    self._available_blocks() - self._reserved:
                return "defer"
            blocks = self._slot_blocks[s]
            for i in range(hits):
                phys = self._prefix_map[hashes[i]]
                if self._ref[phys] == 0:        # was evictable: pin it
                    del self._cached_free[phys]
                self._ref[phys] += 1
                self.block_tables[s, len(blocks)] = phys
                blocks.append(phys)
            if hits:
                self._bt_device = None
            self._reserved += need
            self._slot_reserved[s] = need
            self._slot_hashes[s] = hashes
            self._slot_registered[s] = hits
            if self.prefix_cache:
                self.prefix_stats["lookups"] += 1
                self.prefix_stats["hit_blocks"] += hits
                self.prefix_stats["hit_tokens"] += hits * self.block_size
        self.waiting.pop(qi)
        self._req_hashes.pop(req.rid, None)
        self._hol_bypassed.pop(req.rid, None)
        self.slot_req[s] = req
        self._dev_dirty = True    # slot state rewritten below
        self._slot_admit_iter[s] = self.iteration
        # prefill skips the cached prefix entirely: it resumes at the
        # first cold token via the start_pos chunk path
        self.slot_pos[s] = hits * self.block_size if self.paged else 0
        self.slot_prefill_left[s] = \
            list(tokens_full[int(self.slot_pos[s]):])
        self.slot_out[s] = [] if state is None else list(state.out)
        if state is not None:
            del self._preempted[req.rid]
            if self.slot_prefill_left[s]:
                # the replay list ends one token EARLY (the most recent
                # emitted token was never cached); once its prefill
                # lands, decode must feed that token, not the chunk's
                # last — see _advance_prefill_host
                self._resume_last_tok[req.rid] = state.last_tok
            elif tokens_full:
                self.slot_last_tok[s] = state.last_tok
        elif not self.slot_prefill_left[s] and req.tokens:
            # fully cached prompt: decode can start this same iteration
            # from the last prompt token
            self.slot_last_tok[s] = req.tokens[-1]
        self._mark_admitted(req.rid)
        return "admitted"

    def _try_hol_bypass(self, s: int) -> bool:
        """Head-of-line fix: the FIFO head defers on blocks, but a
        request within the next ``hol_window`` queue positions may fit
        the pool — admit it out of order. Starvation guard: each head
        tolerates at most ``hol_max_bypass`` jumps before the queue
        goes strictly FIFO until it admits."""
        if self.hol_window <= 0 or len(self.waiting) < 2:
            return False
        head_rid = self.waiting[0].rid
        bypasses = self._hol_bypassed.get(head_rid, 0)
        if bypasses >= self.hol_max_bypass:
            return False
        for qi in range(1, min(len(self.waiting), 1 + self.hol_window)):
            if self._try_admit(s, qi, consume=False) == "admitted":
                self._hol_bypassed[head_rid] = bypasses + 1
                self.overload_stats["hol_bypass"] += 1
                return True
        return False

    # -- preemption + host-offload KV tier (DESIGN.md §Overload survival) --
    def _select_victim(self, exclude=()) -> Optional[int]:
        """LIFO victim policy: the most recently admitted DECODING slot
        (mid-prefill slots have not finished paying their admission
        cost), ties broken by the largest remaining worst-case
        reservation — the victim that frees the most future blocks.
        ``exclude`` shields slots the caller must not preempt (the
        reservation-breach path: the slot being grown, plus slots whose
        prefill chunk was already collected for this dispatch)."""
        cands = [s for s in range(self.n_max)
                 if self.slot_req[s] is not None
                 and not self.slot_prefill_left[s] and s not in exclude]
        if not cands:
            return None
        return max(cands, key=lambda s: (self._slot_admit_iter[s],
                                         self._slot_reserved[s], s))

    def preempt_slot(self, s: int, mode: Optional[str] = None,
                     requeue_index: int = 0) -> None:
        """Preempt a DECODING slot: checkpoint its host state, move its
        KV off the device — SWAP (device->host copy of exactly the
        slot's block-table entries; the free list reclaims the device
        blocks) or RECOMPUTE (discard and replay through prefill,
        cheap when the prefix cache still holds the prompt blocks) —
        and re-enqueue it AHEAD of new arrivals. ``mode`` forces
        "swap"/"recompute"; default applies the threshold policy on
        the cold suffix. Resume is handled by _try_admit/_swap_in and
        is bitwise-identical to an unloaded run on the swap path (the
        masked no-op invariant makes a slot's tokens independent of
        its co-tenants; the host copy restores its exact KV bits)."""
        req = self.slot_req[s]
        assert req is not None and not self.slot_prefill_left[s], \
            "can only preempt a decoding slot"
        pos = int(self.slot_pos[s])
        out = list(self.slot_out[s])
        if mode is None:
            restorable = 0
            if self.paged and self.prefix_cache:
                # leading full blocks still content-addressable would
                # be restored by replay, not recomputed
                for h in self._slot_hashes[s]:
                    if h not in self._prefix_map:
                        break
                    restorable += self.block_size
            cold = pos - min(restorable, pos)
            mode = "swap" if cold > self.swap_threshold else "recompute"
        if mode == "swap":
            host_kv = self._swap_out(s)
            n_blocks = len(self._slot_blocks[s]) if self.paged else 0
            self.overload_stats["swapped_out"] += 1
        else:
            host_kv, n_blocks = None, 0
            self.overload_stats["recomputed"] += 1
        # replay reconstructs cache positions 0..pos-1: the prompt, the
        # DUPLICATED last prompt token the first decode dispatch wrote
        # at position P, then all but the newest emitted token (its KV
        # was never written — it is the token decode feeds next)
        replay = list(req.tokens) if not out else \
            list(req.tokens) + [req.tokens[-1]] + out[:-1]
        self._preempted[req.rid] = _PreemptedState(
            req=req, out=out, pos=pos,
            last_tok=int(self.slot_last_tok[s]), replay=replay,
            host_kv=host_kv, n_blocks=n_blocks)
        self.overload_stats["preempted"] += 1
        self._rid_preemptions[req.rid] = \
            self._rid_preemptions.get(req.rid, 0) + 1
        # re-enter the queue AHEAD of new arrivals (requeue_index=1
        # from _admit keeps the currently-deferring head in front);
        # enqueue BEFORE releasing so the idle-point invariant check
        # sees the preempted rid queued
        self.waiting.insert(min(requeue_index, len(self.waiting)), req)
        self._mark_queued(req.rid)
        self.slot_req[s] = None
        self.slot_out[s] = []
        self.slot_pos[s] = 0
        self._dev_dirty = True
        if self.paged:
            self._release_slot(s)

    def _checkpoint_prefilling(self, s: int, requeue_index: int = 0) -> None:
        """Checkpoint a MID-PREFILL slot onto the recompute path (the
        swap tier is pointless here: the KV written so far is a strict
        prefix of what replay re-prefills anyway, and a partial chunk's
        blocks may not even be full). The replay list is rebuilt from
        the ORIGINAL request — not the possibly-already-a-replay the
        slot was prefilling — so checkpointing a resumed request twice
        stays idempotent."""
        req = self.slot_req[s]
        assert req is not None and self.slot_prefill_left[s], \
            "can only checkpoint-prefill a mid-prefill slot"
        out = list(self.slot_out[s])
        replay = list(req.tokens) if not out else \
            list(req.tokens) + [req.tokens[-1]] + out[:-1]
        # a resumed replay parked the true next fed token in
        # _resume_last_tok; a fresh prefill's next fed token is the last
        # prompt token, which is also replay[-1]
        last = self._resume_last_tok.pop(req.rid, None)
        if last is None:
            last = int(replay[-1]) if replay else 0
        self._preempted[req.rid] = _PreemptedState(
            req=req, out=out, pos=0, last_tok=int(last), replay=replay,
            host_kv=None, n_blocks=0)
        self.overload_stats["preempted"] += 1
        self.overload_stats["recomputed"] += 1
        self._rid_preemptions[req.rid] = \
            self._rid_preemptions.get(req.rid, 0) + 1
        self.waiting.insert(min(requeue_index, len(self.waiting)), req)
        self._mark_queued(req.rid)
        self.slot_req[s] = None
        self.slot_out[s] = []
        self.slot_pos[s] = 0
        self.slot_prefill_left[s] = []
        self._dev_dirty = True
        if self.paged:
            self._release_slot(s)

    def drain_checkpoint(self, mode: Optional[str] = None) -> int:
        """Checkpoint EVERY occupied slot into the host tier and
        requeue in slot order AHEAD of already-waiting requests — the
        quiesce step of a live re-provision (DESIGN.md §Live
        re-provisioning). Decoding slots go through preempt_slot (swap
        vs recompute by the cold-suffix threshold, or forced by
        ``mode``); mid-prefill slots are recompute-checkpointed.
        Returns the number of requests checkpointed; afterwards the
        engine holds no slot state and waiting[0:count] are the
        checkpointed requests in slot order."""
        count = 0
        for s in range(self.n_max):
            if self.slot_req[s] is None:
                continue
            if self.slot_prefill_left[s]:
                self._checkpoint_prefilling(s, requeue_index=count)
            else:
                self.preempt_slot(s, mode=mode, requeue_index=count)
            count += 1
        return count

    def _swap_out(self, s: int):
        """Device->host copy of slot ``s``'s KV: exactly its
        block-table entries in paged mode (shared prefix blocks
        included — the host copy must be self-contained, the originals
        may be evicted before resume), or its cache row in dense mode.
        np.asarray forces the transfer; the result aliases no device
        buffer."""
        if self.paged:
            idx = np.array(self._slot_blocks[s], np.int32)
            self.overload_stats["swapped_blocks"] += len(idx)
            if len(idx) == 0:
                return jax.tree.map(
                    lambda a: np.zeros((a.shape[0], 0) + a.shape[2:],
                                       a.dtype), self.cache)
            di = self._upload(idx)
            return jax.tree.map(
                lambda a: np.asarray(L.gather_blocks(a, di)), self.cache)
        return jax.tree.map(
            lambda a: np.asarray(
                L.gather_slot_row(a, s, self._batch_axis(a))), self.cache)

    def _swap_in(self, s: int, qi: int, state: _PreemptedState) -> str:
        """Swap-path resume into free slot ``s``: allocate fresh device
        blocks (the originals were reclaimed), rewrite the block table,
        scatter the host copy back, and restore the slot's host state
        so the next decode continues bitwise where the unloaded run
        would. Defers like a fresh admission when the pool cannot cover
        the request's (unchanged) worst case."""
        req = state.req
        if self.paged:
            worst = self._worst_case_blocks(req)
            if worst > self._available_blocks() - self._reserved:
                return "defer"
            n = state.n_blocks
            fresh = []
            for _ in range(n):
                phys = self._alloc_block()
                self._ref[phys] = 1
                fresh.append(phys)
            self.prefix_stats["allocated_blocks"] += n
            self._slot_blocks[s] = fresh
            self.block_tables[s, :] = 0
            self.block_tables[s, :n] = fresh
            self._bt_device = None
            self._reserved += worst - n
            self._slot_reserved[s] = worst - n
            if n:
                di = self._upload(np.array(fresh, np.int32))
                self.cache = jax.tree.map(
                    lambda c, h: L.scatter_blocks(c, self._upload(h), di),
                    self.cache, state.host_kv)
            # restored blocks re-enter PRIVATE: this slot's prefix
            # registrations were decref'd at preemption, and publishing
            # the new physical copies would duplicate hashes
            self._slot_hashes[s] = []
            self._slot_registered[s] = 0
        else:
            self.cache = jax.tree.map(
                lambda c, h: L.scatter_slot_row(
                    c, self._upload(h), s, self._batch_axis(c)),
                self.cache, state.host_kv)
        if self._cache_shardings is not None:
            # eager scatters above leave the result wherever jax put
            # it; re-pin to the serving shardings before the next step
            self.cache = jax.device_put(self.cache, self._cache_shardings)
        self.waiting.pop(qi)
        del self._preempted[req.rid]
        self._req_hashes.pop(req.rid, None)
        self._hol_bypassed.pop(req.rid, None)
        self.slot_req[s] = req
        self._dev_dirty = True
        self._slot_admit_iter[s] = self.iteration
        self.slot_pos[s] = state.pos
        self.slot_prefill_left[s] = []
        self.slot_out[s] = list(state.out)
        self.slot_last_tok[s] = state.last_tok
        self._mark_admitted(req.rid)
        self.overload_stats["swapped_in"] += 1
        return "admitted"

    def _ensure_blocks(self, s: int, tokens_needed: int) -> bool:
        """Allocate physical blocks for slot ``s`` until it covers
        ``tokens_needed`` positions. Within the slot's admission-time
        reservation the allocatable tiers can never run dry (asserted).
        BEYOND it — only possible under the tightened lout_reservation
        — an allocation may take only the headroom no other slot has
        reserved; returns False (nothing allocated for the breaching
        token) when that headroom is gone, and the caller must free
        blocks via _reservation_breach. Always True on the worst-case
        reservation path."""
        blocks = self._slot_blocks[s]
        while len(blocks) * self.block_size < tokens_needed:
            if self._slot_reserved[s] > 0:
                assert self._free or self._cached_free, \
                    "allocator exhausted despite reservation"
                self._reserved -= 1
                self._slot_reserved[s] -= 1
            elif self._available_blocks() - self._reserved <= 0:
                # other slots' outstanding reservations own every
                # remaining block — taking one would break their
                # never-runs-dry guarantee
                return False
            phys = self._alloc_block()
            self._ref[phys] = 1
            self.block_tables[s, len(blocks)] = phys
            blocks.append(phys)
            self.prefix_stats["allocated_blocks"] += 1
            self._bt_device = None
        return True

    def _reservation_breach(self, s: int, tokens_needed: int,
                            protected=frozenset()) -> None:
        """Slot ``s`` outran its tightened (l_out_hint) reservation and
        the pool has no unreserved headroom: preempt LIFO victims until
        the allocation fits, or — when ``s`` is the only preemptable
        slot left — preempt ``s`` itself (it resumes with a FULL
        worst-case reservation, so a request breaches at most once).
        Never an OOM: the dense worst-case guarantee degrades to a
        preemption, exactly the safety net lout_reservation=True
        contracts for (requires preemption=True, config-validated).
        ``protected`` slots (this dispatch's collected prefill chunks)
        are never victims — their pending chunk would write into a
        released slot."""
        assert self.lout_reservation and self.preemption, \
            "reservation breach outside lout_reservation mode"
        self.overload_stats["reservation_breach"] += 1
        shield = {s} | set(protected)
        while True:
            victim = self._select_victim(exclude=shield)
            if victim is None:
                self.preempt_slot(s, requeue_index=0)
                return
            self.preempt_slot(victim, requeue_index=0)
            if self._ensure_blocks(s, tokens_needed):
                return

    def _block_table_device(self):
        """Device block table, re-uploaded only after allocator writes
        (snapshot semantics: np.array copy, never a live alias);
        REPLICATED across the mesh when sharded — block indices address
        the pool's unsharded physical-block dim, so every device reads
        the same table."""
        if self._bt_device is None:
            self._bt_device = self._upload(np.array(self.block_tables))
        return self._bt_device

    def _release_slot(self, s: int) -> None:
        """DECREMENT the refcount of every block slot ``s`` holds —
        never free outright: a block shared with another live slot (or
        registered in the prefix map) must survive this release. Blocks
        reaching ref == 0 return to the free list if private, or to the
        evictable LRU tier if they hold a registered prefix. Also drops
        the slot's unused worst-case reservation (request finished
        early / at its cap)."""
        for phys in self._slot_blocks[s]:
            self._ref[phys] -= 1
            assert self._ref[phys] >= 0, "refcount underflow"
            if self._ref[phys] == 0:
                if phys in self._block_hash:
                    self._cached_free[phys] = None     # cached, evictable
                else:
                    self._free.append(phys)
        self._reserved -= self._slot_reserved[s]
        self._slot_blocks[s] = []
        self._slot_reserved[s] = 0
        self._slot_hashes[s] = []
        self._slot_registered[s] = 0
        self.block_tables[s, :] = 0
        self._bt_device = None
        if not any(r is not None for r in self.slot_req):
            # engine idle: the refcount invariant must hold exactly
            self.assert_block_invariants()

    def assert_block_invariants(self) -> None:
        """Refcount invariant (ISSUE 4): every physical block sits in
        exactly ONE tier — referenced (ref >= 1), cached-free (ref == 0
        but prefix-registered), or free — and the per-block refcount
        equals its live slot-table occurrences, so

            distinct referenced + len(cached_free) + len(free)
                == num_blocks  (at idle: refs all 0 => the two free
                                tiers partition the pool)

        Cheap (host-side ints); called at engine idle and from tests at
        every iteration. Also covers the host-offload tier (ISSUE 8):
        every preempted rid must be queued for resume, and a swapped
        state's host copy must hold exactly its recorded block count on
        every cache leaf."""
        waiting_rids = {r.rid for r in self.waiting}
        for rid, st in self._preempted.items():
            assert rid in waiting_rids, \
                f"preempted rid {rid} not queued for resume"
            if self.paged and st.host_kv is not None:
                for leaf in jax.tree.leaves(st.host_kv):
                    assert leaf.shape[1] == st.n_blocks, \
                        "host-tier copy disagrees with its block count"
        if not self.paged:
            return
        cnt = Counter(b for blocks in self._slot_blocks for b in blocks)
        for phys in range(self.num_blocks):
            assert self._ref[phys] == cnt.get(phys, 0), \
                f"block {phys}: ref {self._ref[phys]} != " \
                f"{cnt.get(phys, 0)} table entries"
        referenced = set(cnt)
        free, cached = set(self._free), set(self._cached_free)
        assert len(free) == len(self._free), "duplicate in free list"
        assert not referenced & free, "block both referenced and free"
        assert not referenced & cached, "block both referenced and cached"
        assert not free & cached, "block both free and cached-free"
        assert len(referenced) + len(free) + len(cached) == self.num_blocks, \
            "block leak: tiers do not partition the pool"
        assert set(self._prefix_map.values()) == set(self._block_hash), \
            "prefix map and reverse index disagree"
        assert cached <= set(self._block_hash), \
            "cached-free block without a registered hash"
        assert 0 <= self._reserved <= self._available_blocks(), \
            "reservation exceeds allocatable blocks"

    def _prefill_fn(self, decode_impl, params, cache, tokens, start_pos,
                    lengths):
        """One iteration's prefill work for EVERY slot with a pending
        chunk; rows with lengths == 0 are bitwise no-ops."""
        _, cache = M.prefill_chunk(params, self.cfg, tokens, cache,
                                   start_pos, lengths,
                                   decode_impl=decode_impl)
        return self._constrain_cache(cache)

    def _paged_prefill_fn(self, params, cache, tokens, block_tables,
                          start_pos, lengths):
        _, cache = M.paged_prefill_chunk(params, self.cfg, tokens, cache,
                                         block_tables, start_pos, lengths)
        return self._constrain_cache(cache)

    def _bucket_chunks(self, chunks: Dict[int, List[int]]):
        """Pad pending chunks into the smallest covering bucket shape
        (shared by the prefill-only and fused mixed dispatches — the
        bucket choice must be identical for both so each stays within
        the per-bucket compiled-trace bound)."""
        longest = max(len(c) for c in chunks.values())
        bucket = next(b for b in self.buckets if b >= longest)
        self.prefill_buckets_used.add(bucket)
        tokens = np.zeros((self.n_max, bucket), np.int32)
        lengths = np.zeros(self.n_max, np.int32)
        for s, chunk in chunks.items():
            tokens[s, : len(chunk)] = chunk
            lengths[s] = len(chunk)
        return tokens, lengths

    def _run_prefill_chunks(self, chunks: Dict[int, List[int]]) -> None:
        tr = self.tracer
        with tr.span("eng.dispatch", pool=self.name):
            tokens, lengths = self._bucket_chunks(chunks)
            # snapshot slot_pos: jnp.asarray may alias host memory
            # zero-copy and dispatch is async, so passing the live
            # (mutated-below) array would race the device read
            start = np.array(self.slot_pos, np.int32)
            if self.paged:
                self.cache = self._prefill_step(
                    self.params, self.cache, self._upload(tokens),
                    self._block_table_device(), self._upload(start),
                    self._upload(lengths))
            else:
                self.cache = self._prefill_step(
                    self.params, self.cache, self._upload(tokens),
                    self._upload(start), self._upload(lengths))
        self.dispatches += 1
        with tr.span("eng.replay", pool=self.name):
            self._advance_prefill_host(chunks)

    def _advance_prefill_host(self, chunks: Dict[int, List[int]]) -> None:
        """Host bookkeeping for one dispatched chunk per slot (shared
        by the prefill-only and fused mixed paths). Dirties the
        device-resident decode state: slot_pos / slot_last_tok moved
        under the device copy."""
        self._dev_dirty = True
        for s, chunk in chunks.items():
            rid = self.slot_req[s].rid
            self.slot_pos[s] += len(chunk)
            self.moe_rows_total += self._moe_layers * len(chunk)
            self._prefill_iters[rid] = self._prefill_iters.get(rid, 0) + 1
            if not self.slot_prefill_left[s]:
                self.slot_last_tok[s] = chunk[-1]
                if rid in self._resume_last_tok:
                    # recompute-path resume: the replay deliberately
                    # stops one token early (the newest emitted token's
                    # KV was never written); decode must feed IT next,
                    # not the replay's final token
                    self.slot_last_tok[s] = self._resume_last_tok.pop(rid)
            if self.paged and self.prefix_cache:
                # full prompt blocks this chunk completed become
                # content-addressable for later admissions
                self._register_prefix_blocks(s)

    def _batch_axis(self, leaf) -> int:
        # dense kv caches (L,B,S,H,hd) + int8 scales (L,B,S,H) -> 1;
        # VLM grouped kv (G,E,B,S,H,hd) -> 2; anything else -> 0
        if leaf.ndim in (4, 5):
            return 1
        if leaf.ndim == 6:
            return 2
        return 0

    def _decode_fn(self, decode_impl, params, cache, tokens, pos, active):
        logits, cache = M.decode_step(params, self.cfg, tokens, cache, pos,
                                      decode_impl=decode_impl, active=active)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), \
            self._constrain_cache(cache)

    def _paged_decode_fn(self, decode_impl, params, cache, tokens,
                         block_tables, pos, active):
        logits, cache = M.paged_decode_step(params, self.cfg, tokens, cache,
                                            block_tables, pos,
                                            decode_impl=decode_impl,
                                            active=active)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), \
            self._constrain_cache(cache)

    # -- multi-step decode scan (DESIGN.md §Engine hot path) ---------------
    def _scan_body(self, decode_impl, params, block_tables, carry):
        """One decode micro-iteration inside the K-step lax.scan:
        masked decode_step + on-device argmax + on-device termination.
        A row that finishes (budget spent / EOS / c_max) flips its own
        active bit and freezes via the no-op invariant — the remaining
        micro-iterations leave its cache row bit-identical."""
        cache, tok, pos, active, budget = carry
        if block_tables is None:
            logits, cache = M.decode_step(
                params, self.cfg, tok[:, None], cache, pos,
                decode_impl=decode_impl, active=active)
        else:
            logits, cache = M.paged_decode_step(
                params, self.cfg, tok[:, None], cache, block_tables, pos,
                decode_impl=decode_impl, active=active)
        # keep every micro-iteration's carry pinned to the cache
        # shardings (a drifting layout inside the scan would insert a
        # reshard collective per step)
        cache = self._constrain_cache(cache)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # -1 marks rows that emitted nothing this micro-iteration; the
        # host replay stops at the first -1 per row
        emitted = jnp.where(active, nxt, -1)
        tok = jnp.where(active, nxt, tok)
        pos = jnp.where(active, pos + 1, pos)
        budget = jnp.where(active, budget - 1, budget)
        # exact mirror of the host-side completion rule: budget spent
        # (len(out) reached max_new), EOS emitted, or context full
        done = budget <= 0
        if self.eos_id is not None:
            done = done | (tok == self.eos_id)
        done = done | (pos >= self.c_max)
        active = active & ~done
        return (cache, tok, pos, active, budget), emitted

    def _decode_scan_fn(self, decode_impl, k, params, cache, tok, pos,
                        active, budget):
        def body(carry, _):
            return self._scan_body(decode_impl, params, None, carry)
        carry, emitted = jax.lax.scan(
            body, (cache, tok, pos, active, budget), None, length=k)
        return carry, emitted.T            # (B, K) emitted tokens

    def _paged_decode_scan_fn(self, decode_impl, k, params, cache, tok,
                              block_tables, pos, active, budget):
        def body(carry, _):
            return self._scan_body(decode_impl, params, block_tables, carry)
        carry, emitted = jax.lax.scan(
            body, (cache, tok, pos, active, budget), None, length=k)
        return carry, emitted.T

    # -- speculative verify scan (DESIGN.md §Speculative decoding) ---------
    def _spec_body(self, decode_impl, w_max, params, block_tables, drafts,
                   dlen, carry):
        """One speculative verify micro-iteration inside the K-step
        scan: feed [last_tok, next w draft tokens] through the masked
        multi-token verify step, accept the longest draft prefix that
        matches the model's own greedy argmax, and emit it plus the
        bonus token — a per-row DYNAMIC advance of 1..w_max tokens
        through the same carry the plain scan uses.

        The draft table is walked by a per-row cursor: a row whose
        window fully accepts continues from the next draft tokens; a
        row whose draft dies burns the rest of its drafts (cursor ->
        dlen) and degenerates to plain 1-token decode for the remaining
        micro-iterations — no separate code path, just lengths == 1.
        Inactive rows feed lengths == 0 and stay provable bitwise
        no-ops, exactly like finished slots in the plain scan."""
        cache, tok, pos, active, budget, cur = carry
        w_d = w_max - 1                       # draft tokens per window
        idx = jnp.clip(cur[:, None] + jnp.arange(w_d)[None, :], 0,
                       drafts.shape[1] - 1)
        dwin = jnp.take_along_axis(drafts, idx, axis=1)      # (B, W-1)
        # feedable draft count: leftover drafts, clipped so the window
        # (drafts + bonus token) can never outrun the row's remaining
        # budget or the context — the same termination quantities the
        # plain scan checks AFTER emitting, checked BEFORE here
        w = jnp.minimum(dlen - cur,
                        jnp.minimum(budget - 1, self.c_max - 1 - pos))
        w = jnp.where(active, jnp.clip(w, 0, w_d), 0)
        fed = jnp.concatenate([tok[:, None], dwin], axis=1)  # (B, W)
        lengths = jnp.where(active, 1 + w, 0)
        if block_tables is None:
            logits, cache = M.verify_step(
                params, self.cfg, fed, cache, pos, lengths,
                decode_impl=decode_impl)
        else:
            logits, cache = M.paged_verify_step(
                params, self.cfg, fed, cache, block_tables, pos, lengths)
        cache = self._constrain_cache(cache)
        g = jnp.argmax(logits, axis=-1).astype(jnp.int32)    # (B, W)
        # draft i (fed position i+1) is accepted iff it equals the
        # model's continuation at the previous position; j = longest
        # accepted prefix. Because accepted drafts EQUAL g, emitting
        # g[0..j] is bitwise the sequence plain decode would produce.
        match = (dwin == g[:, :w_d]) \
            & (jnp.arange(w_d)[None, :] < w[:, None])
        j = jnp.cumprod(match.astype(jnp.int32), axis=1).sum(axis=1)
        emit = (jnp.arange(w_max)[None, :] <= j[:, None]) & active[:, None]
        if self.eos_id is not None:
            # truncate the window at the first emitted EOS — the host
            # releases the slot there, so the device must not advance
            # past it either (host/device lockstep)
            is_eos = (g == self.eos_id).astype(jnp.int32)
            emit &= (jnp.cumsum(is_eos, axis=1) - is_eos) == 0
        emitted = jnp.where(emit, g, -1)
        e = emit.sum(axis=1).astype(jnp.int32)   # >= 1 for active rows
        last = jnp.take_along_axis(
            g, jnp.clip(e - 1, 0, w_max - 1)[:, None], axis=1)[:, 0]
        tok = jnp.where(active & (e > 0), last, tok)
        pos = pos + e
        budget = budget - e
        # cursor: a fully-accepted window emits its bonus token too, and
        # the host drafted a prediction for that position (d[cur+w]) —
        # if the bonus confirms it, the continuation is still alive and
        # the next window resumes AFTER it (cur+w+1); any divergence
        # (partial accept, or bonus != predicted) kills the rest of the
        # row's drafts, because they all extend the dead continuation
        d_next = jnp.take_along_axis(
            drafts, jnp.clip(cur + w, 0, drafts.shape[1] - 1)[:, None],
            axis=1)[:, 0]
        chain = (j >= w) & (cur + w < dlen) & (d_next == last)
        cur = jnp.where(chain, cur + w + 1, dlen)
        done = (budget <= 0) | (pos >= self.c_max)
        if self.eos_id is not None:
            done = done | (emit & (g == self.eos_id)).any(axis=1)
        active = active & ~done
        return (cache, tok, pos, active, budget, cur), (emitted, w)

    def _spec_scan_fn(self, decode_impl, k, w_max, params, cache, tok,
                      pos, active, budget, drafts, dlen):
        def body(carry, _):
            return self._spec_body(decode_impl, w_max, params, None,
                                   drafts, dlen, carry)
        cur = jnp.zeros_like(dlen)
        carry, outs = jax.lax.scan(
            body, (cache, tok, pos, active, budget, cur), None, length=k)
        return carry, outs          # ((K, B, W) emitted, (K, B) fed)

    def _paged_spec_scan_fn(self, decode_impl, k, w_max, params, cache,
                            tok, block_tables, pos, active, budget,
                            drafts, dlen):
        def body(carry, _):
            return self._spec_body(decode_impl, w_max, params,
                                   block_tables, drafts, dlen, carry)
        cur = jnp.zeros_like(dlen)
        carry, outs = jax.lax.scan(
            body, (cache, tok, pos, active, budget, cur), None, length=k)
        return carry, outs

    def _mixed_fn(self, decode_impl, params, cache, tokens, pos, lengths,
                  decode_toks, active):
        logits, cache = M.mixed_step(params, self.cfg, tokens, cache, pos,
                                     lengths, decode_toks, active,
                                     decode_impl=decode_impl)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), \
            self._constrain_cache(cache)

    def _paged_mixed_fn(self, decode_impl, params, cache, tokens,
                        block_tables, pos, lengths, decode_toks, active):
        logits, cache = M.paged_mixed_step(params, self.cfg, tokens, cache,
                                           block_tables, pos, lengths,
                                           decode_toks, active,
                                           decode_impl=decode_impl)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), \
            self._constrain_cache(cache)

    def _device_decode_state(self, mask: np.ndarray):
        """Device-resident (tok, pos, active, budget), re-uploaded ONLY
        when host bookkeeping wrote slot state since the last scan
        dispatch. The upload snapshots host arrays (np.array copies —
        the async-aliasing rule from PR 1: a zero-copy jnp.asarray of a
        live host buffer would race later in-place host updates)."""
        if self._dev_dirty or self._dev_state is None:
            budget = np.zeros(self.n_max, np.int32)
            for s in range(self.n_max):
                req = self.slot_req[s]
                if req is not None:
                    budget[s] = req.max_new_tokens - len(self.slot_out[s])
            self._dev_state = (
                self._upload(np.array(self.slot_last_tok, np.int32)),
                self._upload(np.array(self.slot_pos, np.int32)),
                self._upload(np.array(mask)),
                self._upload(budget))
            self._dev_dirty = False
        return self._dev_state

    def _finish_slot(self, s: int) -> None:
        req = self.slot_req[s]
        self.results[req.rid] = ServeResult(
            rid=req.rid, output_tokens=self.slot_out[s],
            prefill_iters=self._prefill_iters.pop(req.rid, 0),
            decode_iters=len(self.slot_out[s]),
            queue_iters=self._queue_iters.pop(req.rid, 0),
            preemptions=self._rid_preemptions.pop(req.rid, 0))
        self._completed_total += 1
        self.slot_req[s] = None
        if self.paged:
            self._release_slot(int(s))

    def _append_token(self, s: int, tok: int) -> bool:
        """Host mirror of one emitted token; returns True when the slot
        completed (same rule the device scan applies)."""
        req = self.slot_req[s]
        self._count_decode_row(int(self.slot_pos[s]))
        self.slot_out[s].append(tok)
        self.slot_last_tok[s] = tok
        self.slot_pos[s] += 1
        self.decode_tokens_emitted += 1
        return (len(self.slot_out[s]) >= req.max_new_tokens
                or (self.eos_id is not None and tok == self.eos_id)
                or self.slot_pos[s] >= self.c_max)

    def _count_decode_row(self, pos: int) -> None:
        """The model counters for one decode row at position ``pos``."""
        self.moe_rows_total += self._moe_layers
        if self._kernel_pages:
            bs = self.block_size
            end = -(-(pos + 1) // bs)
            self.attn_pages_swept["full"] += self._full_layers * end
            self.attn_pages_swept["window"] += sum(
                end - max(pos + 1 - w, 0) // bs for w in self._windows)

    def _run_decode(self, mask: np.ndarray) -> None:
        tr = self.tracer
        with tr.span("eng.dispatch", pool=self.name):
            # snapshot host state (see _run_prefill_chunks: async
            # dispatch must never observe the in-place updates below)
            toks = self._upload(np.array(self.slot_last_tok[:, None]))
            pos = self._upload(np.array(self.slot_pos))
            if self.paged:
                next_tok, self.cache = self._decode(
                    self.params, self.cache, toks,
                    self._block_table_device(), pos, self._upload(mask))
            else:
                next_tok, self.cache = self._decode(
                    self.params, self.cache, toks, pos, self._upload(mask))
        self.dispatches += 1
        self.decode_dispatches += 1
        self._decode_only_tokens += int(mask.sum())
        self._dev_dirty = True
        with tr.span("eng.sync", pool=self.name):
            next_tok = np.asarray(next_tok)
        with tr.span("eng.replay", pool=self.name):
            for s in np.where(mask)[0]:
                if self._append_token(int(s), int(next_tok[s])):
                    self._finish_slot(int(s))

    def _run_decode_scan(self, mask: np.ndarray) -> None:
        """One dispatch, ``decode_k`` decode iterations: the lax.scan
        micro-loop samples, terminates and freezes rows on device;
        the only sync is the batched (n_max, K) emitted-token pull.
        The host replays the same completion rule over the batch to
        update its mirrors WITHOUT re-dirtying the device copy."""
        k = self.decode_k
        tr = self.tracer
        with tr.span("eng.dispatch", pool=self.name):
            tok, pos, active, budget = self._device_decode_state(mask)
            if self.paged:
                carry, emitted = self._decode_scan(
                    self.params, self.cache, tok,
                    self._block_table_device(), pos, active, budget)
            else:
                carry, emitted = self._decode_scan(
                    self.params, self.cache, tok, pos, active, budget)
        self.cache = carry[0]
        self._dev_state = carry[1:]
        self.dispatches += 1
        self.decode_dispatches += 1
        with tr.span("eng.sync", pool=self.name):
            emitted = np.asarray(emitted)      # the single host sync
        self.iteration += k - 1                # step() already added 1
        with tr.span("eng.replay", pool=self.name):
            for s in np.where(mask)[0]:
                s = int(s)
                done = False
                for j in range(k):
                    t = int(emitted[s, j])
                    if t < 0:
                        break
                    self._occ_slot_iters += 1
                    self._decode_only_tokens += 1
                    done = self._append_token(s, t)
                    if done:
                        break
                if done:
                    self._finish_slot(s)
                # a row that stayed live emitted every micro-iteration,
                # so the per-token occupancy increments above already
                # credit it with all k iterations

    def _run_spec_scan(self, mask: np.ndarray) -> None:
        """One dispatch, ``decode_k`` speculative verify iterations
        (DESIGN.md §Speculative decoding): the host proposes ONE
        n-gram draft continuation per slot, the jitted scan verifies
        it window by window, and the single sync pulls the
        (K, n_max, spec_k) emitted-token tensor. The host replays
        per WINDOW (the flat emitted stream is -1-padded per window,
        not prefix-terminated like the plain scan's), applying the
        same completion rule so the device and host mirrors stay in
        exact lockstep."""
        k, w_max = self.decode_k, self.spec_k
        # ceiling consumption per dispatch: every window can feed
        # w_max-1 drafts AND chain its bonus through one more (the
        # cursor's cur+w+1 advance), so k windows can walk k*w_max - 1
        # drafts when the continuation never diverges
        m_len = k * w_max - 1
        drafts = np.zeros((self.n_max, m_len), np.int32)
        dlen = np.zeros(self.n_max, np.int32)
        for s in np.where(mask)[0]:
            s = int(s)
            req = self.slot_req[s]
            # a draft token is only useful if the budget/context also
            # admits its bonus token — clip at the source so proposals
            # never exceed the remaining budget (property-test pinned)
            cap = min(m_len,
                      req.max_new_tokens - len(self.slot_out[s]) - 1,
                      self.c_max - 1 - int(self.slot_pos[s]))
            if cap <= 0:
                continue
            d = propose_draft(list(req.tokens) + self.slot_out[s], cap,
                              self.spec_ngram)
            if d:
                drafts[s, :len(d)] = d
                dlen[s] = len(d)
                self.spec_stats["drafted_tokens"] += len(d)
        tr = self.tracer
        with tr.span("eng.dispatch", pool=self.name):
            tok, pos, active, budget = self._device_decode_state(mask)
            d_dev = self._upload(drafts)
            n_dev = self._upload(dlen)
            if self.paged:
                carry, (emitted, fed) = self._decode_scan(
                    self.params, self.cache, tok,
                    self._block_table_device(), pos, active, budget,
                    d_dev, n_dev)
            else:
                carry, (emitted, fed) = self._decode_scan(
                    self.params, self.cache, tok, pos, active, budget,
                    d_dev, n_dev)
        self.cache = carry[0]
        # the carry's draft cursor is per-dispatch scratch; only the
        # (tok, pos, active, budget) slot state persists on device
        self._dev_state = carry[1:5]
        self.dispatches += 1
        self.decode_dispatches += 1
        with tr.span("eng.sync", pool=self.name):
            emitted = np.asarray(emitted)    # (K, n_max, W) — the sync
            fed = np.asarray(fed)            # (K, n_max) drafts fed
        self.iteration += k - 1              # step() already added 1
        with tr.span("eng.replay", pool=self.name):
            for s in np.where(mask)[0]:
                s = int(s)
                done = False
                for m in range(k):
                    e = 0
                    for i in range(w_max):
                        t = int(emitted[m, s, i])
                        if t < 0:
                            break
                        e += 1
                        self._decode_only_tokens += 1
                        done = self._append_token(s, t)
                        if done:
                            break
                    if e:
                        # one live verify window == one occupied model
                        # iteration, however many tokens it accepted —
                        # utilization stays comparable across kappa
                        self._occ_slot_iters += 1
                        self.spec_stats["proposed_tokens"] += int(fed[m, s])
                        self.spec_stats["accepted_tokens"] += e - 1
                        self.spec_stats["verify_windows"] += 1
                    if done:
                        break
                if done:
                    self._finish_slot(s)

    def spec_acceptance_rate(self) -> float:
        """Accepted / proposed draft tokens over all verify windows
        (0.0 before any window ran). ``proposed`` counts what was FED
        to the verifier — drafts clipped away by budget/context never
        reach a window and are not charged."""
        if self.spec_stats["proposed_tokens"] == 0:
            return 0.0
        return (self.spec_stats["accepted_tokens"]
                / self.spec_stats["proposed_tokens"])

    def spec_kappa(self) -> float:
        """Measured mean tokens emitted per verify iteration (>= 1.0;
        1.0 = speculation never accepted anything). This is the kappa
        ``HardwareProfile.spec_kappa`` wants for effective-tokens/s
        fleet sizing."""
        w = self.spec_stats["verify_windows"]
        if w == 0:
            return 1.0
        return (self.spec_stats["accepted_tokens"] + w) / w

    def _run_mixed(self, chunks: Dict[int, List[int]],
                   mask: np.ndarray) -> None:
        """Fused prefill+decode dispatch: ONE jitted call advances all
        pending chunks AND all decode rows (M.mixed_step) — the mixed
        iteration previously cost two host dispatches."""
        tr = self.tracer
        with tr.span("eng.dispatch", pool=self.name):
            tokens, lengths = self._bucket_chunks(chunks)
            # snapshot host state (async-dispatch aliasing rule)
            pos = self._upload(np.array(self.slot_pos, np.int32))
            toks = self._upload(np.array(self.slot_last_tok[:, None]))
            if self.paged:
                next_tok, self.cache = self._mixed(
                    self.params, self.cache, self._upload(tokens),
                    self._block_table_device(), pos, self._upload(lengths),
                    toks, self._upload(mask))
            else:
                next_tok, self.cache = self._mixed(
                    self.params, self.cache, self._upload(tokens), pos,
                    self._upload(lengths), toks, self._upload(mask))
        self.dispatches += 1
        self._dev_dirty = True
        with tr.span("eng.sync", pool=self.name):
            next_tok = np.asarray(next_tok)
        with tr.span("eng.replay", pool=self.name):
            self._advance_prefill_host(chunks)
            for s in np.where(mask)[0]:
                if self._append_token(int(s), int(next_tok[s])):
                    self._finish_slot(int(s))
