"""Batched serving engine: continuous-batching slots over the decode step,
run under Pliant control.

Each slot holds one request's progress; finished slots are refilled from the
queue without stopping the batch ("continuous batching"). Admission is
chunked prefill: the prompt streams through fixed-size full-sequence chunks
(``serve.prefill``) — no O(prompt) token-by-token warmup on the decode path,
so 32k prompts admit in a handful of executable calls.

Two cache data models, selected by ``paged``:

* **dense** (default): per-slot ``max_len`` rings; admission prefills a
  single-request cache and slot-scatters it (``serve.slots``).
* **paged**: a shared physical page pool + per-slot block tables
  (``serve.pages.PagePool`` owns allocation host-side; the jitted paths in
  ``models.attention`` gather/scatter through the tables). Admission maps
  shared prompt-prefix pages copy-on-write — a prefix hit SKIPS those
  prefill chunks entirely — and prefills the remainder straight into the
  pool; completion returns pages to the free list. The pool budget is a
  Pliant knob: the engine binds itself to an attached ``PliantRuntime`` as
  a ``core.tenant.ServeTenant`` whose reclaimable quanta are pool pages —
  RECLAIM/RETURN shrink/regrow ``pool_pages``, evicting prefix-cache pages
  first and never touching live requests.

The paged loop is **continuously batched** and **stall-free**: admission
prefill never runs to completion inside ``step()``. EVERY free slot opens
its own in-flight admission each step (no wave barrier — freed slots refill
while their neighbours keep decoding), and the step advances the in-flight
admissions round-robin under a QoS-aware chunk budget: ONE bounded chunk
per step while any decoder is live (unless the attached runtime's
``LatencyMonitor`` reports p99 comfortably inside the QoS target — the
``qos_guard`` band), bursting up to ``max_admission_chunks`` when there is
no decoder to protect or headroom to spare. A long prompt therefore adds at
most one budget's worth of work between any two decode steps. The decode
executable takes a per-slot ``active`` mask so admitting slots' dead batch
rows cannot scatter garbage into their (already mapped) pages or SSM rows.
Admission is also **page-aware packed**: when the head of the queue does
not fit the pool budget, the first of the leading ``pack_window`` pending
requests that does fit is admitted instead — and after ``max_head_skips``
consecutive head skips admission reverts to strict FIFO, so head-of-line
blocking AND starvation are both bounded. Admission allocates grouped:
prompt pages AND the request's projected decode pages map in one free-list
transaction (``serve.pages``), so the decode hot loop almost never touches
the allocator; a per-step ``PagePool.replenish`` keeps free-list headroom
above a watermark by evicting prefix entries off the admission path.
Banded-attention archs (every attention layer LOCAL) skip the speculative
reservation — they free pages that fall out of the window as decode
advances, keeping pool occupancy flat for long generations. On device,
hybrid decode is ONE fused executable per step (attention pages and SSM
rows advance inside a single lowered scan — ``models.lm.decode_step``);
single-device engines use dynamic-index cache writes and, when greedy,
fuse argmax into the step so only (B,) token ids cross the host boundary.
The dense path keeps the legacy synchronous admission (its slot-insert is
exact-output-critical).

Serving variants come from a ``VariantTable`` (the explorer's serving grid):
every variant's decode executable is registered up front and the active one
is swapped at a step boundary — an O(µs) dictionary lookup, the DynamoRIO
function-pointer swap analogue. When a ``PliantRuntime`` is attached, the
engine feeds per-token latency to its ``LatencyMonitor``, ticks the arbiter
at step boundaries, and receives its decisions back through the tenant
protocol (``request_variant`` — deferred while an admission is in flight),
converting cache dtype when a swap crosses the ``kv_quant`` boundary. A
multi-tenant runtime (``launch/colocate.py``) attaches the same way via
``attach_runtime``. Under a mesh, params shard via
``dist.param_shardings`` and caches via ``dist.cache_shardings``.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import threading
import time
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.approx.knobs import ApproxKnobs, PRECISE
from repro.configs.base import LOCAL_ATTN, MAMBA, ModelConfig, ShapeConfig
from repro.core import tenant as tenant_mod
from repro.core.runtime import PliantRuntime
from repro.core.variants import VariantTable
from repro.dist import elastic
from repro.models import lm
from repro.models.attention import PagedKVCache
from repro.models.mamba2 import MambaCache
from repro.serve import pages as pages_mod
from repro.serve import slots as slots_mod
from repro.train import step as step_mod


@dataclass
class Request:
    uid: int
    prompt: List[int]
    max_new: int = 16
    out: List[int] = field(default_factory=list)
    done: bool = False
    t_arrival: float = 0.0    # driver-set (open-loop client)
    t_enqueue: float = 0.0    # stamped by submit(): admission-timeout clock
    t_admit_start: float = 0.0  # first prefill chunk issued (queue-wait ends)
    t_admit: float = 0.0      # admission COMPLETION (prefill done, slot live)
    admit_compute_s: float = 0.0  # pure prefill executable time (no queueing,
                                  # no interleaved decode steps)
    token_times: List[float] = field(default_factory=list)
    rejected: bool = False    # structured rejection (never silently dropped)
    rejection: Optional["AdmissionTimeout"] = None


@dataclass(frozen=True)
class AdmissionTimeout:
    """Structured admission rejection: the request waited in the queue past
    the engine's ``admission_timeout_s`` bound without ever fitting the pool.
    Attached to ``Request.rejection``, collected on ``engine.rejected``, and
    counted in ``engine.stats`` — a rejection is an explicit, attributable
    outcome, never a request that silently vanished under pressure."""
    uid: int
    waited_s: float
    queue_depth: int       # pending queue length at rejection time
    step: int              # engine step at which the timeout fired


@dataclass
class _Admission:
    """One in-flight background admission (continuous-batching loop): the
    prompt's prefill progress, advanced chunk-by-chunk under the per-step
    QoS budget. Several may be in flight at once — one per free slot."""
    req: Request
    slot: int
    next: int                    # next prompt index to prefill
    stops: List[int]             # ascending pause points; last == len(prompt)
    mamba_register: List[int]    # boundaries registered WITH an SSM snapshot
    tail_register: List[int]     # boundaries registered after completion
    logits: object = None
    compute_s: float = 0.0
    started: bool = False        # first chunk issued (queue-wait ends THEN,
                                 # not when the admission is opened)


@dataclass
class ServeEngine:
    cfg: ModelConfig
    batch_slots: int
    max_len: int
    knobs: ApproxKnobs = PRECISE       # single-variant mode (no table)
    temperature: float = 0.0           # 0.0 = greedy
    params: object = None
    table: Optional[VariantTable] = None
    runtime: Optional[PliantRuntime] = None
    mesh: object = None
    policy: str = "tp"                 # param sharding policy under a mesh
    prefill_chunk: int = 16
    seed: int = 0
    cache_dtype: object = jnp.float32
    paged: bool = False                # paged pool instead of dense rings
    page_size: int = 8
    n_pages: int = 0                   # 0 = auto (serve.pages.spec_for)
    use_kernel: Optional[bool] = None  # paged-attention dispatch override
                                       # (None = fused kernel on TPU only)
    kernel_interpret: bool = False     # Pallas interpret mode (CPU CI of the
                                       # sharded kernel path)
    max_prefill_exes: int = 16         # LRU bound on admission executables
    pack_window: int = 4               # pending requests scanned per step for
                                       # page-aware packing (bounds host work
                                       # while the pool is blocked)
    max_head_skips: int = 64           # packing fairness: after this many
                                       # head-of-queue skips, admit strict
                                       # FIFO so a large request cannot be
                                       # starved by a stream of small ones
    max_admission_chunks: int = 4      # prefill-chunk burst per step when no
                                       # decoder needs protecting (or QoS
                                       # headroom says bursting is safe)
    qos_guard: float = 0.25            # guard band: burst only while monitor
                                       # p99 <= (1 - guard) * QoS target
    admission_timeout_s: float = 0.0   # 0 = wait forever; > 0 = reject a
                                       # never-admitted request after this
                                       # long with a structured
                                       # AdmissionTimeout (engine.rejected)
    backoff_base: int = 1              # steps before retrying a pool-blocked
    backoff_cap: int = 8               # request; doubles per failure, capped
    background_compile: bool = True    # AOT-compile surviving-mesh decode
                                       # during a revocation's grace window
    megastep_k: int = 0                # > 0: fuse up to K decode steps per
                                       # dispatch (lax.scan megastep with
                                       # on-device sampling/stop masking +
                                       # async double-buffered host loop);
                                       # paged engines only. 0 = per-step
    eos_id: int = -1                   # stop-token id (-1 = none): a row
                                       # emitting it finishes early, on
                                       # device mid-megastep or on host in
                                       # the per-step path — same contract
    sync_timing: bool = False          # drain each megastep before
                                       # dispatching the next: no pipeline
                                       # overlap, but per-token stamps
                                       # measure compute, not enqueue
                                       # (benchmarks set this)
    donate: bool = True                # donate cache buffers into decode /
                                       # megastep / admission executables
                                       # (in-place pool + SSM update — no
                                       # per-step full-cache copy); rebuilt
                                       # executables re-donate after an
                                       # elastic re-home or variant swap

    def __post_init__(self):
        if self.runtime is not None:
            self.table = self.runtime.table
        self._variant_knobs = ([v.knobs for v in self.table.variants]
                               if self.table is not None else [self.knobs])
        self._active = 0
        self.pool: Optional[pages_mod.PagePool] = None
        self._page_spec = None
        self.stores: List[pages_mod.CacheStore] = []
        # greedy paged engines fuse argmax into the decode executable: the
        # step returns (B,) token ids, so the host never pulls (B, V) logits
        self._fused_sample = bool(self.paged and self.temperature <= 0.0)
        self._derive_plans()
        if self.paged:
            self._page_spec = pages_mod.spec_for(
                self.batch_slots, self.max_len, self.page_size, self.n_pages,
                n_shards=self._plan_shards())
            self.pool = pages_mod.PagePool(self._page_spec, self.batch_slots)
            # one store per cache kind behind the shared CacheStore protocol:
            # the page pool for attention state, the trivial per-slot store
            # for SSM state — the engine frees every kind uniformly
            self.stores = [self.pool]
            if MAMBA in self.cfg.pattern:
                self.stores.append(pages_mod.MambaSlotStore())
        self._derive_shardings()
        if self._param_sh is not None:
            with self._ctx():
                self.params = jax.device_put(self.params, self._param_sh)

        # the variant table of decode executables: registered once up front,
        # hot-swapped between steps (no recompilation on the critical path).
        # Engine-owned, never written into the (possibly shared) table —
        # executables are lowered against THIS engine's mesh/shardings.
        # Paged engines take the per-slot ``active`` write mask so decode
        # can interleave with background admission (stall-free loop). Under
        # a mesh the fused kernel runs shard_map'd over the slot-affinity
        # pool when the decode plan allows; otherwise the attention layer
        # takes the GSPMD gather path and logs why (attention.explain_
        # dispatch reports the decision up front).
        self._decodes: Dict[int, object] = {
            i: None for i in range(len(self._variant_knobs))}
        self._build_decodes()
        # admission executables, keyed by (knobs, chunk len, paged) — NOT by
        # variant index, so table entries with identical admission knobs
        # share one compiled chunk cell — and LRU-bounded
        self._prefills: "collections.OrderedDict[Tuple, object]" = \
            collections.OrderedDict()
        self._insert = jax.jit(slots_mod.insert_request)

        self.caches = self._init_caches(self.active_knobs.kv_quant)
        self.positions = np.zeros(self.batch_slots, np.int32)
        self.slots: List[Optional[Request]] = [None] * self.batch_slots
        self.pending: Deque[Request] = collections.deque()
        # in-flight background admissions, keyed by slot (insertion order =
        # admission order): continuous batching keeps one per free slot
        self._admissions: Dict[int, _Admission] = {}
        # admissions whose LAST chunk is dispatched but not yet drained:
        # first-token sampling waits for the step's single drain point so
        # the final chunk's compute overlaps the decode dispatched after it
        self._await_admit: Dict[int, _Admission] = {}
        # ---- megastep pipeline state (megastep_k > 0) ----
        if self.megastep_k:
            assert self.paged, "megastep decode requires the paged engine"
        self._megasteps: Dict[Tuple[int, int], object] = {}  # (variant, k)
        self._inflight: Optional[dict] = None  # dispatched, undrained round
        self._carry = None             # device (cur, pos, alive, draws,
                                       # budget) chained between dispatches;
                                       # None = cold-start from host mirrors
        self._inject_slots: Set[int] = set()   # slots (re)activated since
                                               # the last dispatch: their
                                               # carry rows merge from host
        self._uids = np.zeros(self.batch_slots, np.int32)  # sampler stream
        self._pos_ub = np.zeros(self.batch_slots, np.int32)  # exclusive ub
                                       # on positions in-flight megasteps
                                       # may write (page pre-map horizon)
        self.decode_dispatches = 0     # decode/megastep executable calls
        self.row_dispatches = 0        # per-row dispatch count: a row in a
        self.row_tokens = 0            # drain with n>=1 tokens adds (1, n)
                                       # — dispatches/token = 1.0 per-step,
                                       # ~1/K under a sustained megastep
        self.drain_block_s = 0.0       # wall spent blocked at drain points
        self._head_skips = 0           # consecutive pool-blocked head-of-queue
        # window-exit page freeing is sound only when EVERY attention layer
        # is banded (a single global/shared layer still reaches every page)
        self._window_free = (self.cfg.window if self.paged and self.cfg.window
                             and set(self.cfg.pattern) <= {LOCAL_ATTN, MAMBA}
                             else 0)
        self.cur_tokens = np.zeros(self.batch_slots, np.int32)
        self.step_latencies: List[float] = []
        self.admit_latencies: List[float] = []
        self.swaps: List[Tuple[int, int]] = []   # (step index, variant index)
        self.step_admission_chunks: List[Tuple[int, int]] = []  # (used, budget)
        self._token_lat: List[float] = []        # unflushed monitor samples
        # per-request PRNG streams keyed (engine seed, uid): sampling is
        # invariant to slot assignment and admission interleaving, so
        # continuous batching reproduces the wave-scheduled token streams
        self._rngs: Dict[int, np.random.Generator] = {}
        self._pending_variant: Optional[int] = None
        # ---- elasticity / fault state (dist.elastic) ----
        self.step_count = 0
        self._base_mesh = self.mesh          # full-capacity mesh (restore)
        self._revoked: Set[int] = set()      # device ids currently revoked
        self._pending_capacity: List[Tuple[int, object]] = []  # (due, event)
        self._collective_failures = 0        # queued transient step failures
        self._recovering: List[dict] = []    # rehome entries awaiting first
                                             # completed decode step
        self.elastic_log: List[dict] = []
        self._prepared: Dict[Tuple, object] = {}   # AOT-compiled decodes for
        self._compile_threads: List[threading.Thread] = []  # a pending mesh
        # admission backoff/timeout state
        self._backoff: Dict[int, Tuple[int, int]] = {}  # uid -> (retry, dly)
        self.rejected: List[Request] = []
        self.stats: Dict[str, int] = dict(
            admission_timeouts=0, backoff_skips=0, collective_retries=0,
            capacity_events=0, rehomes=0)
        self._tenant = None
        self._bound = False
        if (self.runtime is not None and self.runtime.auto_tenant
                and self.runtime.reshard_fn is None):
            # bind this engine as the runtime's tenant (replacing the
            # constructor's placeholder wrap — unless the caller supplied
            # their own reshard actuator, which stays in charge of quanta):
            # variant hot-swaps arrive via ``request_variant`` and — for
            # paged engines — pool_pages is the tenant's reclaimable quanta
            # (RECLAIM shrinks the page budget, prefix cache evicted first;
            # RETURN grows it back)
            self._tenant = tenant_mod.ServeTenant(engine=self)
            self.runtime.bind(self._tenant)
            self._bound = True

    # ------------------------------------------------------------- layout --
    # Every mesh-dependent decision is (re)derived by the helpers below —
    # at construction AND again by ``_rehome`` when a capacity event changes
    # the mesh. Nothing about the layout is cached anywhere else.

    def _derive_plans(self) -> None:
        """Slot-affinity decode plan + ring-prefill sequence plan, decided
        from (cfg, CURRENT mesh, slots/chunk) by the pure plan functions the
        traced steps re-derive — no side channel."""
        self._decode_plan, self._plan_reason = None, "single device"
        self._prefill_plan, self._prefill_reason = None, "single device"
        if self.mesh is None:
            return
        from repro.dist import sharding as dist_sharding
        if self.paged:
            self._decode_plan, self._plan_reason = \
                dist_sharding.paged_decode_plan(
                    self.cfg, self.mesh, self.batch_slots, self.n_pages)
        self._prefill_plan, self._prefill_reason = \
            dist_sharding.prefill_plan(self.cfg, self.mesh,
                                       self.prefill_chunk)

    def _plan_shards(self) -> int:
        return (self._decode_plan.n_shards
                if self._decode_plan is not None else 1)

    def _derive_shardings(self) -> None:
        self._param_sh = self._cache_sh = None
        if self.mesh is None:
            return
        from repro.dist import sharding as dist_sharding
        self._param_sh = dist_sharding.param_shardings(
            self.cfg, self.mesh, self.policy)
        shp = ShapeConfig("serve", self.max_len, self.batch_slots, "decode")
        self._cache_sh, _ = dist_sharding.cache_shardings(
            self.cfg, shp, self.mesh, paged=self._page_spec)

    def _decode_builder(self):
        if self.paged:
            return functools.partial(
                step_mod.make_paged_serve_step,
                mesh=self.mesh,
                use_kernel=self.use_kernel,
                interpret=self.kernel_interpret,
                dynamic_scatter=self.mesh is None,
                sample_greedy=self._fused_sample)
        return step_mod.make_serve_step

    def _mesh_key(self, mesh) -> Tuple:
        if mesh is None:
            return ("1x1",)
        return (tuple(sorted(mesh.shape.items())),
                tuple(int(d.id) for d in np.asarray(mesh.devices).ravel()))

    def _build_decodes(self) -> None:
        """(Re)lower the decode executable of every REGISTERED variant
        against the current mesh/shardings (retired variants stay retired).
        jit is lazy, so rebuilding the whole dict costs wrapper setup only —
        compilation happens at each variant's first post-(re)build call,
        except where ``_prepared`` holds an AOT executable background-
        compiled during a revocation grace window."""
        mk = self._decode_builder()
        mkey = self._mesh_key(self.mesh)
        prepared = getattr(self, "_prepared", {})   # post-init ordering
        self._decodes = {
            i: (prepared.pop((mkey, i), None)
                or self._lower_decode(mk(self.cfg, self._variant_knobs[i])))
            for i in self._decodes}

    # ----------------------------------------------------------- dispatch --

    @property
    def sharded_kernel(self) -> bool:
        """True when this engine's decode executable runs the fused kernel
        shard_map'd over the slot-affinity pool (the multi-device fast
        path), False for single-device kernels and gather fallbacks."""
        if not self.paged or self._decode_plan is None:
            return False
        if self.use_kernel is not None:
            return bool(self.use_kernel)
        from repro.kernels import ops as kops
        return kops._on_tpu()

    def explain_dispatch(self) -> str:
        """One-line paged-decode dispatch description (startup banner)."""
        from repro.models import attention as attn_mod
        if not self.paged:
            return "dense decode: ring caches (no paged dispatch)"
        return attn_mod.explain_dispatch(
            self.cfg, self.mesh, batch_slots=self.batch_slots,
            n_pages=self._page_spec.n_pages, use_kernel=self.use_kernel,
            megastep_k=self.megastep_k if self.paged else 0)

    def explain_megastep(self) -> str:
        """One-line megastep/pipeline description (startup banner)."""
        if not self.paged or self.megastep_k <= 0:
            return "megastep: off (one decode dispatch per token)"
        samp = ("greedy argmax" if self.temperature <= 0.0 else
                f"temperature categorical, (seed,uid,draw) fold-in "
                f"seed={self.seed}")
        return (f"megastep: up to {self.megastep_k} tokens fused per "
                f"dispatch (lax.scan), on-device {samp} + EOS/budget stop "
                f"masking, cache donation {'ON' if self.donate else 'OFF'}, "
                + ("sync-timing drain (no overlap)" if self.sync_timing
                   else "async double-buffered host pipeline"))

    @property
    def sharded_prefill(self) -> bool:
        """True when this engine's admission chunks run the ring-attention
        sequence-parallel cell (full-size chunks; ragged tails re-plan)."""
        if self._prefill_plan is None:
            return False
        if self.use_kernel is not None:
            return bool(self.use_kernel)
        from repro.kernels import ops as kops
        return kops._on_tpu()

    def explain_prefill_dispatch(self) -> str:
        """One-line chunked-prefill dispatch description (startup banner)."""
        from repro.models import attention as attn_mod
        return attn_mod.explain_prefill_dispatch(
            self.cfg, self.mesh, chunk_len=self.prefill_chunk,
            use_kernel=self.use_kernel)

    # ------------------------------------------------------------ variants --

    @property
    def active_variant(self) -> int:
        return self._active

    @property
    def active_knobs(self) -> ApproxKnobs:
        return self._variant_knobs[self._active]

    def set_variant(self, idx: int) -> None:
        """Hot-swap the decode executable at a step boundary, converting the
        KV rings/pages when the swap crosses the ``kv_quant`` boundary."""
        if idx == self._active:
            return
        old, new = self.active_knobs, self._variant_knobs[idx]
        if old.kv_quant != new.kv_quant:
            with self._ctx():
                self.caches = slots_mod.convert_caches(
                    self.caches, new.kv_quant, self.cache_dtype)
                if self._cache_sh is not None:
                    self.caches = jax.device_put(self.caches, self._cache_sh)
        if self.pool is not None and old != new:
            # prefix entries are tagged by the knobs that computed them; a
            # swap re-encodes the pool in place, so drop the stale index
            self.pool.flush_prefixes()
        self._active = idx
        self.swaps.append((len(self.step_latencies), idx))

    def request_variant(self, idx: int) -> None:
        """Tenant-protocol actuation: hot-swap at the next SAFE step
        boundary. Swaps are deferred while an admission is in flight — a
        mid-prompt knob change would mix admission executables (and prefix
        tags) within one request."""
        self._pending_variant = idx
        self._apply_pending_variant()

    def _apply_pending_variant(self) -> None:
        # undrained admissions (_await_admit) count as in flight: their
        # prefix tags / logits came from the old knobs
        if (self._pending_variant is None or self._admissions
                or self._await_admit):
            return
        idx, self._pending_variant = self._pending_variant, None
        if idx != self._active:
            self.set_variant(idx)

    def attach_runtime(self, runtime: PliantRuntime,
                       tenant=None) -> None:
        """Attach a pre-built (multi-tenant) runtime AFTER construction —
        the colocate harness builds engine -> ServeTenant -> runtime in
        that order. The engine then drives the control loop (latency feed
        + decision ticks at its step boundaries); actuation arrives back
        through ``tenant`` (this engine's adapter in the runtime's list,
        located automatically when omitted). A multi-tenant runtime MUST
        contain this engine's adapter: the unbound fallback polls
        ``states[0]``, which would apply ANOTHER tenant's variant index to
        this engine."""
        if tenant is None:
            tenant = next((t for t in runtime.tenants
                           if isinstance(t, tenant_mod.ServeTenant)
                           and t.engine is self), None)
        assert tenant is not None or len(runtime.tenants) == 1, \
            "multi-tenant runtime has no ServeTenant for this engine"
        self.runtime = runtime
        self._tenant = tenant
        self._bound = tenant is not None

    def retire_variant(self, idx: int) -> None:
        """Drop a retired table entry's executables. Admission cells are
        knobs-keyed, so they survive while any live variant shares the
        knobs and are evicted with the last user."""
        assert idx != self._active, "cannot retire the active variant"
        self._decodes.pop(idx, None)
        kn = self._variant_knobs[idx]
        if any(k == kn for i, k in enumerate(self._variant_knobs)
               if i != idx and i in self._decodes):
            return
        for key in [k for k in self._prefills if k[0] == kn]:
            del self._prefills[key]

    def _lower_decode(self, step):
        # donate the caches argument: the pool/SSM state updates in place
        # instead of being copied whole per step (the dominant decode HBM
        # cost at high occupancy). Donation is an executable property, so a
        # rebuild (_rehome, variant swap) re-donates automatically; the
        # collective-failure retry path copies first (_call_decode)
        cidx = 4 if self.paged else 3
        kw = dict(donate_argnums=(cidx,)) if self.donate else {}
        if self.mesh is None:
            return jax.jit(step, **kw)
        if self.paged:      # (params, tokens, position, active, caches)
            return jax.jit(step,
                           in_shardings=(self._param_sh, None, None, None,
                                         self._cache_sh),
                           out_shardings=(None, self._cache_sh), **kw)
        return jax.jit(step,
                       in_shardings=(self._param_sh, None, None,
                                     self._cache_sh),
                       out_shardings=(None, self._cache_sh), **kw)

    def _prefill_exe(self, chunk_len: int):
        key = (self.active_knobs, chunk_len, self.paged)
        fn = self._prefills.get(key)
        if fn is not None:
            self._prefills.move_to_end(key)
            return fn
        # the caches argument (position 3 in both admission signatures)
        # donates like the decode path: a chunked prefill updates the pool /
        # fresh single-request cache in place instead of copying it per chunk
        kw = dict(donate_argnums=(3,)) if self.donate else {}
        if self.paged:
            step = step_mod.make_paged_admission_step(
                self.cfg, self.active_knobs,
                dynamic_scatter=self.mesh is None, mesh=self.mesh,
                use_kernel=self.use_kernel, interpret=self.kernel_interpret)
            if self.mesh is None:
                fn = jax.jit(step, **kw)
            else:
                fn = jax.jit(step,
                             in_shardings=(self._param_sh, None, None,
                                           self._cache_sh, None),
                             out_shardings=(None, self._cache_sh), **kw)
        else:
            step = step_mod.make_admission_step(
                self.cfg, self.active_knobs, mesh=self.mesh,
                use_kernel=self.use_kernel, interpret=self.kernel_interpret)
            if self.mesh is None:
                fn = jax.jit(step, **kw)
            else:
                fn = jax.jit(step, in_shardings=(self._param_sh, None, None,
                                                 None), **kw)
        self._prefills[key] = fn
        while len(self._prefills) > self.max_prefill_exes:
            self._prefills.popitem(last=False)
        return fn

    # ------------------------------------------------------------- helpers --

    def _ctx(self):
        if self.mesh is None:
            return contextlib.nullcontext()
        return jax.set_mesh(self.mesh)

    def _init_caches(self, quantized: bool):
        if self.paged:
            sp = self._page_spec
            caches = lm.init_paged_caches(
                self.cfg, self.batch_slots, sp.n_pages, sp.page_size,
                sp.max_pages, dtype=self.cache_dtype, quantized=quantized)
        else:
            caches = lm.init_caches(self.cfg, self.batch_slots, self.max_len,
                                    dtype=self.cache_dtype,
                                    quantized=quantized)
        if self._cache_sh is not None:
            with self._ctx():
                caches = jax.device_put(caches, self._cache_sh)
        return caches

    def _rng_for(self, req: Request) -> np.random.Generator:
        g = self._rngs.get(req.uid)
        if g is None:
            g = np.random.default_rng((self.seed, req.uid))
            self._rngs[req.uid] = g
        return g

    def _sample_rows(self, logits: np.ndarray,
                     reqs: List[Request]) -> np.ndarray:
        """ONE batched sampling call for every emitting row (the per-row
        numpy loop cost O(slots) softmax passes per step). logits: (R, V);
        ``reqs`` the emitting requests, row-aligned. Greedy is a single
        argmax; temperature sampling draws one uniform per request from its
        PRIVATE stream and inverts the softmax CDF — exactly the tokens a
        per-row loop over the same streams would produce, regardless of
        which rows happen to share the batch."""
        if self.temperature <= 0.0:
            return np.argmax(logits, axis=-1)
        z = logits.astype(np.float64) / self.temperature
        z -= z.max(axis=-1, keepdims=True)
        p = np.exp(z)
        cdf = np.cumsum(p, axis=-1)
        u = np.asarray([self._rng_for(r).random() for r in reqs])
        idx = (cdf < u[:, None] * cdf[:, -1:]).sum(axis=-1)
        return np.minimum(idx, logits.shape[-1] - 1)

    def submit(self, req: Request) -> None:
        req.t_enqueue = req.t_enqueue or time.perf_counter()
        self.pending.append(req)

    # ---------------------------------------------------------- elasticity --

    def inject(self, ev, *, notify_runtime: bool = True) -> None:
        """Entry point for a ``dist.elastic.CapacityEvent`` (fault injector,
        driver, or tenant adapter). A revocation with a grace deadline is
        deferred to ``step + deadline_steps``: through the grace window the
        engine keeps serving on the doomed mesh while the runtime — notified
        here — treats the pending loss as contention (the variant ladder
        degrades through the normal Fig. 3 loop instead of traffic being
        rejected) and the surviving-mesh executables start compiling in the
        background. Everything else applies at the next step boundary.
        ``notify_runtime=False`` is for tenant adapters whose runtime
        already saw the event (``PliantRuntime.inject`` fans out both
        ways)."""
        self.stats["capacity_events"] += 1
        if notify_runtime and self.runtime is not None:
            self.runtime.notify_capacity(ev)
        due = self.step_count
        if ev.kind == elastic.REVOKE and ev.deadline_steps > 0:
            due += ev.deadline_steps
            self.elastic_log.append(dict(
                step=self.step_count, kind="revoke_notice", count=ev.count,
                devices=list(ev.devices), deadline_step=due))
            if self.background_compile and self.paged \
                    and self._base_mesh is not None:
                self._precompile_async(ev)
        self._pending_capacity.append((due, ev))

    def _process_capacity(self) -> None:
        """Apply every capacity event whose (grace) deadline has arrived —
        called at the top of ``step()``, so cutovers happen at step
        boundaries only."""
        if not self._pending_capacity:
            return
        due = [e for s, e in self._pending_capacity if s <= self.step_count]
        self._pending_capacity = [(s, e) for s, e in self._pending_capacity
                                  if s > self.step_count]
        for ev in due:
            self._apply_capacity(ev)

    def _apply_capacity(self, ev) -> None:
        entry = dict(step=self.step_count, kind=ev.kind)
        if ev.kind in (elastic.REVOKE, elastic.RESTORE):
            if self._base_mesh is None:
                # single-device engine: no mesh to shrink — the event still
                # flowed to the runtime as pressure, which is all it can mean
                entry["ignored"] = "no mesh"
                self.elastic_log.append(entry)
                return
            if ev.kind == elastic.REVOKE:
                ids = ev.devices or elastic.pick_revoked(
                    self.mesh if self.mesh is not None else self._base_mesh,
                    ev.count, already=self._revoked)
                self._revoked |= {int(i) for i in ids}
            else:
                self._revoked -= ({int(i) for i in ev.devices}
                                  if ev.devices else set(self._revoked))
            new_mesh, why = elastic.surviving_mesh(
                self._base_mesh, self._revoked,
                prefer_divisor_of=self.batch_slots)
            entry.update(self._rehome(new_mesh, why))
            entry["revoked"] = sorted(self._revoked)
            self._recovering.append(entry)
        elif ev.kind == elastic.QUOTA_CUT:
            if self.pool is not None:
                self.pool.set_capacity_cut(self.pool.capacity_cut + ev.quanta)
                entry["capacity_cut"] = self.pool.capacity_cut
        elif ev.kind == elastic.QUOTA_RESTORE:
            if self.pool is not None:
                cut = (self.pool.capacity_cut - ev.quanta if ev.quanta else 0)
                self.pool.set_capacity_cut(max(cut, 0))
                entry["capacity_cut"] = self.pool.capacity_cut
        elif ev.kind == elastic.COLLECTIVE_FAILURE:
            self._collective_failures += max(ev.count, 1)
            entry["queued_failures"] = self._collective_failures
        self.elastic_log.append(entry)

    def _rehome(self, new_mesh, why: str = "") -> dict:
        """Cut the LIVE engine over to ``new_mesh`` (shrink on revocation,
        grow on restore) without dropping anything. All durable decode state
        is mesh-shape-independent — (pool, caches, positions, cur_tokens,
        admission chunk cursors) — only WHERE the arrays live changes:

        1. re-derive the layout plans/shardings for the new mesh (the same
           pure functions construction uses; an infeasible plan degrades
           loudly to the gather/unsharded path, it never corrupts);
        2. migrate the page pool (``PagePool.migrate``: live pages re-homed
           onto their slots' new affinity shards, prefix entries evicted)
           and permute the host-staged device caches to match;
        3. re-put params under the new shardings (host-staged — the revoked
           devices may be gone);
        4. rebuild the decode executables (AOT background-compiled ones are
           picked up when ready; the rest compile lazily at first call) and
           drop the admission-cell LRU — in-flight ``_Admission``s simply
           resume at their chunk cursor on the new mesh."""
        t0 = time.perf_counter()
        # flush the async pipeline first: the in-flight megastep's tokens
        # must land (and its donated-cache chain settle) before the caches
        # are host-staged; the device carry is invalidated — the first
        # dispatch on the new mesh cold-starts from the host mirrors
        self._drain_pipeline()
        # in-flight admission logits live on the old mesh — host-stage them
        # (drain-deferred completions in _await_admit included)
        for adm in list(self._admissions.values()) \
                + list(self._await_admit.values()):
            if adm.logits is not None:
                adm.logits = np.asarray(adm.logits)
        old_shards = self._plan_shards() if self.paged else 1
        self.mesh = new_mesh
        self._derive_plans()
        migrated = 0
        if self.paged:
            new_spec = pages_mod.spec_for(
                self.batch_slots, self.max_len, self.page_size, self.n_pages,
                n_shards=self._plan_shards())
            new_pool, perm = self.pool.migrate(new_spec)
            self._page_spec = new_spec
            self._derive_shardings()
            self.caches = self._migrate_paged_caches(perm, new_pool)
            self.pool = new_pool
            self.stores[0] = new_pool
            migrated = int((perm >= 0).sum())
        else:
            self._derive_shardings()
            with self._ctx():
                self.caches = elastic.reshard_live(self.caches,
                                                   self._cache_sh)
        with self._ctx():
            self.params = elastic.reshard_live(self.params, self._param_sh)
        self._build_decodes()
        self._prefills.clear()
        self._megasteps.clear()    # lowered against the old mesh/shardings;
                                   # rebuilt (and re-donated) lazily
        self.stats["rehomes"] += 1
        return dict(
            step_index=len(self.step_latencies), why=why,
            mesh_shape=(dict(new_mesh.shape) if new_mesh is not None
                        else None),
            n_shards=(old_shards, self._plan_shards() if self.paged else 1),
            pages_migrated=migrated,
            cutover_s=time.perf_counter() - t0,
            recovery_steps=None, _t_rehome=t0)

    def _migrate_paged_caches(self, perm: np.ndarray, new_pool):
        """Host-stage the old device caches and permute the physical-page
        axis into the new pool's layout: ``perm[new_pid] = old_pid`` source
        (-1 = starts empty — zero KV, -1 positions, masked out of
        attention). Leaves are group-stacked, so the page dim is axis 1;
        Mamba rows are slot-major and pass through unchanged. The staged
        copy is the only surviving reference once the old devices go."""
        dst = np.flatnonzero(perm >= 0)
        src = perm[dst]
        bt = np.asarray(new_pool.blocks)

        def move(x, fill):
            x = np.asarray(jax.device_get(x))
            out = np.full((x.shape[0], new_pool.spec.n_pages) + x.shape[2:],
                          fill, x.dtype)
            out[:, dst] = x[:, src]
            return out

        caches = []
        for c in self.caches:
            if isinstance(c, PagedKVCache):
                caches.append(PagedKVCache(
                    kp=move(c.kp, 0), vp=move(c.vp, 0),
                    ppos=move(c.ppos, -1),
                    block=np.broadcast_to(
                        bt[None], (np.shape(c.block)[0],) + bt.shape).copy()))
            else:
                caches.append(elastic.host_stage(c))
        caches = tuple(caches)
        with self._ctx():
            if self._cache_sh is not None:
                return jax.device_put(caches, self._cache_sh)
            return jax.tree.map(jnp.asarray, caches,
                                is_leaf=lambda x: isinstance(x, np.ndarray))

    def _precompile_async(self, ev) -> None:
        """Best-effort AOT compile of the ACTIVE variant's decode executable
        for the mesh that survives ``ev``, on a background thread during the
        revocation grace window — the cutover's first step then skips the
        full compile. Any failure just falls back to lazy compilation at
        cutover; correctness never depends on this racing to finish."""
        lost = self._revoked | set(ev.devices or elastic.pick_revoked(
            self.mesh if self.mesh is not None else self._base_mesh,
            ev.count, already=self._revoked))
        new_mesh, _ = elastic.surviving_mesh(
            self._base_mesh, lost, prefer_divisor_of=self.batch_slots)
        if new_mesh is None:
            return
        variant = self._active
        key = (self._mesh_key(new_mesh), variant)
        if key in self._prepared:
            return

        def compile_target():
            try:
                from repro.dist import sharding as dist_sharding
                plan, _ = dist_sharding.paged_decode_plan(
                    self.cfg, new_mesh, self.batch_slots, self.n_pages)
                spec = pages_mod.spec_for(
                    self.batch_slots, self.max_len, self.page_size,
                    self.n_pages,
                    n_shards=plan.n_shards if plan is not None else 1)
                psh = dist_sharding.param_shardings(self.cfg, new_mesh,
                                                    self.policy)
                shp = ShapeConfig("serve", self.max_len, self.batch_slots,
                                  "decode")
                csh, _ = dist_sharding.cache_shardings(
                    self.cfg, shp, new_mesh, paged=spec)
                step = step_mod.make_paged_serve_step(
                    self.cfg, self._variant_knobs[variant], mesh=new_mesh,
                    use_kernel=self.use_kernel,
                    interpret=self.kernel_interpret, dynamic_scatter=False,
                    sample_greedy=self._fused_sample)
                sds = lambda t: jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(np.shape(x),
                                                   np.asarray(x).dtype
                                                   if not hasattr(x, "dtype")
                                                   else x.dtype), t)
                caches_abs = jax.eval_shape(functools.partial(
                    lm.init_paged_caches, self.cfg, self.batch_slots,
                    spec.n_pages, spec.page_size, spec.max_pages,
                    dtype=self.cache_dtype,
                    quantized=self._variant_knobs[variant].kv_quant))
                B = self.batch_slots
                kw = dict(donate_argnums=(4,)) if self.donate else {}
                exe = jax.jit(
                    step, in_shardings=(psh, None, None, None, csh),
                    out_shardings=(None, csh), **kw
                ).lower(
                    sds(self.params),
                    jax.ShapeDtypeStruct((B, 1), jnp.int32),
                    jax.ShapeDtypeStruct((B,), jnp.int32),
                    jax.ShapeDtypeStruct((B,), jnp.bool_),
                    caches_abs,
                ).compile()
                self._prepared[key] = exe
            except Exception as e:     # pragma: no cover - best effort
                self.elastic_log.append(dict(
                    step=self.step_count, kind="precompile_failed",
                    error=repr(e)))

        th = threading.Thread(target=compile_target, daemon=True)
        self._compile_threads.append(th)
        th.start()

    def _expire_pending(self) -> None:
        """Admission-timeout sweep: reject (structured, loud in stats) every
        queued request that has waited past ``admission_timeout_s`` without
        ever being admitted. In-flight admissions are never expired — they
        are making progress by construction (chunked prefill advances every
        budgeted step)."""
        if self.admission_timeout_s <= 0 or not self.pending:
            return
        now = time.perf_counter()
        keep: Deque[Request] = collections.deque()
        for req in self.pending:
            t0 = req.t_enqueue or req.t_arrival
            if t0 and now - t0 > self.admission_timeout_s:
                req.rejected = True
                req.rejection = AdmissionTimeout(
                    uid=req.uid, waited_s=now - t0,
                    queue_depth=len(self.pending), step=self.step_count)
                self.rejected.append(req)
                self.stats["admission_timeouts"] += 1
                self._backoff.pop(req.uid, None)
                self._rngs.pop(req.uid, None)
            else:
                keep.append(req)
        self.pending = keep

    # ------------------------------------------------------ paged plumbing --

    def _free_slot(self, slot: int) -> bool:
        """Release a finished request's cache residency across every store.
        Returns True when device-visible mapping state changed."""
        dirty = False
        for store in self.stores:
            dirty |= store.free_slot(slot)
        return dirty

    def _push_blocks(self) -> None:
        """Mirror the host block tables into the device caches (host-side
        allocation between steps; jitted steps only read the tables) and
        scrub freed pages' stale positions before they can be reused."""
        bt = jnp.asarray(self.pool.blocks)
        scrub = self.pool.drain_scrub()
        pids = jnp.asarray(scrub, jnp.int32) if scrub else None

        def one(c):
            if isinstance(c, PagedKVCache):
                ppos = c.ppos if pids is None else \
                    c.ppos.at[:, pids].set(-1)
                return c._replace(
                    ppos=ppos,
                    block=jnp.broadcast_to(bt[None], c.block.shape))
            return c

        self.caches = tuple(one(c) for c in self.caches)
        if self._cache_sh is not None:
            with self._ctx():
                self.caches = jax.device_put(self.caches, self._cache_sh)

    def _mamba_snapshot(self, slot: int):
        """Host copy of the slot's SSM state rows (prefix-boundary snapshot
        carried by the prefix index; None for attention-only archs)."""
        snap = {}
        for ci, c in enumerate(self.caches):
            if isinstance(c, MambaCache):
                snap[ci] = MambaCache(*(np.asarray(x[:, slot]) for x in c))
        return snap or None

    def _set_mamba_rows(self, slot: int, snap) -> None:
        """Seed the slot's SSM rows for a fresh admission: the prefix-entry
        snapshot on a hit, zeros otherwise — the previous tenant's state must
        never leak into a new request (the dense path gets this for free
        from its fresh single-request cache + insert)."""
        if not any(isinstance(c, MambaCache) for c in self.caches):
            return
        caches = list(self.caches)
        for ci, c in enumerate(self.caches):
            if not isinstance(c, MambaCache):
                continue
            row = snap.get(ci) if snap else None
            caches[ci] = MambaCache(*(
                x.at[:, slot].set(jnp.zeros_like(x[:, slot]) if r is None
                                  else jnp.asarray(r))
                for x, r in zip(c, row or (None,) * len(c))))
        self.caches = tuple(caches)
        if self._cache_sh is not None:
            with self._ctx():
                self.caches = jax.device_put(self.caches, self._cache_sh)

    # ----------------------------------------------------------- admission --

    def _chunked_prefill(self, prompt: List[int]):
        """Dense path: stream the prompt through fixed-size chunks into a
        fresh single-request cache. Returns (last-token logits, caches)."""
        knobs = self.active_knobs
        caches = lm.init_caches(self.cfg, 1, self.max_len,
                                dtype=self.cache_dtype,
                                quantized=knobs.kv_quant)
        toks = np.asarray(prompt, np.int32)
        S, start, logits = len(prompt), 0, None
        with self._ctx():
            while start < S:
                C = min(self.prefill_chunk, S - start)
                logits, caches = self._prefill_exe(C)(
                    self.params, jnp.asarray(toks[None, start:start + C]),
                    jnp.asarray(start, jnp.int32), caches)
                start += C
        return logits, caches

    def _prefix_dedup_wait(self, req: Request, shard: int = 0) -> bool:
        """Cold-start prefix dedup: True when an in-flight admission is
        prefilling a page-aligned prefix this prompt shares and the index
        does not cover it yet. Admitting now would concurrently re-prefill
        (and re-allocate) pages the sibling is about to register — hold the
        request back until the registration lands. Steady state (prefix
        already indexed) never defers, so warm traces keep full admission
        concurrency. Only siblings on the SAME pool shard count: a prefix
        registered on another shard's pages can never be mapped here (slot
        affinity), so waiting on it would be pure latency."""
        P = self.page_size
        cap = min((len(req.prompt) - 1) // P, self.pool.max_register_pages)
        if cap <= 0 or not self._admissions:
            return False
        best = 0
        for adm in self._admissions.values():
            if self.pool.slot_shard(adm.slot) != shard:
                continue
            other = adm.req.prompt
            lim = min(len(req.prompt), len(other), cap * P)
            k = 0
            while k < lim and req.prompt[k] == other[k]:
                k += 1
            best = max(best, (k // P) * P)
        if not best:
            return False
        return self.pool.lookup_prefix(req.prompt, self.active_knobs,
                                       shard)[0] < best

    def _start_admissions(self, count_skips: bool = True) -> None:
        """Open a background admission on EVERY free slot (continuous
        batching — no wave barrier: a slot freed this step refills this
        step). Per slot, pick the first of the leading ``pack_window``
        pending requests whose pages fit the pool budget (page-aware
        packing — a pool-blocked head of queue must not stall admissions
        that fit) and whose shared prefix is not mid-prefill in a sibling
        admission (``_prefix_dedup_wait``). The window bounds the per-step host work while the pool
        is blocked, and after ``max_head_skips`` consecutive head skips
        admission falls back to strict FIFO so a large request cannot be
        starved by a stream of small ones. Maps the block table grouped —
        prompt pages plus projected decode pages in one transaction; prefix
        hits bump refcounts and skip those chunks — and seeds the slot's
        SSM rows; prefill itself is advanced by ``_advance_admissions``.
        Does NOT stamp ``t_admit_start``: queue-wait ends when the first
        chunk RUNS (``_advance_one``), not when the admission is opened."""
        started_any = False
        while self.pending:
            slot = next((i for i in range(self.batch_slots)
                         if self.slots[i] is None
                         and i not in self._admissions
                         and i not in self._await_admit), None)
            if slot is None:
                break
            strict = self._head_skips >= self.max_head_skips
            window = 1 if strict else min(len(self.pending), self.pack_window)
            started = False
            for qi in range(window):
                req = self.pending[qi]
                assert len(req.prompt) <= self.max_len, \
                    (len(req.prompt), self.max_len)
                assert len(req.prompt) + req.max_new <= \
                    self._page_spec.max_pages * self.page_size, \
                    "paged serving does not ring-wrap: need " \
                    "max_len >= prompt + max_new"
                if self._prefix_dedup_wait(req, self.pool.slot_shard(slot)):
                    continue       # sibling is mid-prefill of our prefix
                bo = self._backoff.get(req.uid)
                if bo is not None and self.step_count < bo[0]:
                    # bounded backoff: a pool-blocked request sits out its
                    # (exponentially grown, capped) window instead of
                    # re-running the admit feasibility gate every step
                    self.stats["backoff_skips"] += 1
                    continue
                # grouped/speculative allocation: reserve the decode pages
                # up front (positions S .. S+max_new-2 are written) so the
                # hot loop's ensure_decode_page never allocates. Banded
                # archs skip the reservation — they free window-dead pages
                # to hold occupancy flat, and pre-mapping the whole decode
                # horizon would defeat that
                reserve = 0 if self._window_free else max(req.max_new - 1, 0)
                plan = self.pool.admit(slot, req.prompt, self.active_knobs,
                                       reserve_tokens=reserve)
                if plan is None:
                    delay = (min(bo[1] * 2, self.backoff_cap) if bo
                             else max(self.backoff_base, 1))
                    self._backoff[req.uid] = (self.step_count + delay, delay)
                    if qi == 0 and count_skips:
                        self._head_skips += 1
                    continue                 # over budget: try the next one
                self._backoff.pop(req.uid, None)
                if qi == 0:
                    self._head_skips = 0
                del self.pending[qi]
                snap = plan.entry.mamba if (plan.shared_tokens and plan.entry)\
                    else None
                self._set_mamba_rows(slot, snap)
                has_mamba = any(isinstance(c, MambaCache)
                                for c in self.caches)
                S = len(req.prompt)
                if has_mamba:
                    # prefill pauses at each boundary so its SSM snapshot
                    # matches
                    stops = sorted(set(plan.register) | {S})
                    mamba_reg, tail_reg = list(plan.register), []
                else:
                    # attention-only: pages are position-addressed,
                    # registration is pure bookkeeping — no need to fragment
                    # the chunk stream
                    stops = [S]
                    mamba_reg, tail_reg = [], list(plan.register)
                self._admissions[slot] = _Admission(
                    req, slot, plan.shared_tokens, stops, mamba_reg, tail_reg)
                started = started_any = True
                break
            if not started:
                break       # nothing in the window fits — later slots share
                            # the same pool, so stop scanning this step
        if started_any:
            # ONE block-table push covers every admission opened this call
            self._push_blocks()

    def _chunk_budget(self) -> int:
        """Prefill chunks this step may spend across all in-flight
        admissions — the QoS-aware knob that trades time-to-first-token
        against inter-token latency. No live decoder: burst (nobody's
        inter-token gap to protect). Otherwise one chunk, unless the
        runtime's monitor has a tail estimate comfortably inside the QoS
        target (p99 at most (1 - qos_guard) x target): with that much
        headroom, admissions may burst without endangering the guarantee.
        An abstaining monitor (below min_samples) or no runtime at all
        means no evidence — stay conservative."""
        cap = max(1, self.max_admission_chunks)
        if not any(s is not None for s in self.slots):
            return cap
        from repro.core.controller import headroom_burst
        if headroom_burst(self.runtime, self.qos_guard):
            return cap
        return 1

    def _megastep_budget(self) -> int:
        """Decode tokens the next megastep may fuse — K as a Pliant-visible
        knob, bounded by the same guard band as ``_chunk_budget`` but
        pulling the OTHER way: large K amortizes dispatch overhead
        (throughput), small K keeps admission interleaving fine-grained and
        lets a de-approximation decision (variant swap, reclaim) take
        effect within one token instead of K. With admission work pending
        the megastep shrinks to 1 unless the monitor shows measured
        headroom (``controller.headroom_burst``); with nothing to
        interleave, full K always. Queued work that CANNOT start — every
        slot occupied, nothing in flight — is not admission work: shrinking
        K for it would serialize the whole first wave at K=1 for nothing."""
        cap = max(1, self.megastep_k)
        admitting = bool(self._admissions or self._await_admit)
        can_start = bool(self.pending) and any(
            self.slots[i] is None and i not in self._admissions
            and i not in self._await_admit
            for i in range(self.batch_slots))
        if not (admitting or can_start):
            return cap
        from repro.core.controller import headroom_burst
        if headroom_burst(self.runtime, self.qos_guard):
            return cap
        return 1

    def _advance_admissions(self) -> None:
        """Continuous-batching admission phase of ``step()``: open
        admissions on free slots, then advance the in-flight set round-robin
        one chunk at a time until the step's QoS chunk budget is spent (or
        nothing is left to advance). Completions free their slot mid-phase,
        so the re-scan between passes can immediately refill it — several
        short prompts can admit back-to-back within one step's budget."""
        budget = self._chunk_budget()
        used = 0
        self._start_admissions()
        while used < budget:
            ran = False
            for slot in list(self._admissions):
                if used >= budget:
                    break
                self._advance_one(self._admissions[slot])
                used += 1
                ran = True
            if not ran:
                break
            self._start_admissions(count_skips=False)
        if used or self._admissions:
            self.step_admission_chunks.append((used, budget))

    def _advance_one(self, adm: _Admission) -> None:
        """Run ONE bounded prefill chunk of ``adm``; on the final chunk,
        sample the first token and activate the slot."""
        req = adm.req
        if not adm.started:
            adm.started = True
            req.t_admit_start = time.perf_counter()   # queue-wait ends HERE
        S = len(req.prompt)
        end = next(b for b in adm.stops if b > adm.next)
        C = min(self.prefill_chunk, end - adm.next)
        toks = np.asarray(req.prompt[adm.next:adm.next + C], np.int32)
        t0 = time.perf_counter()
        with self._ctx():
            adm.logits, self.caches = self._prefill_exe(C)(
                self.params, jnp.asarray(toks[None]),
                jnp.asarray(adm.next, jnp.int32), self.caches,
                jnp.asarray(adm.slot, jnp.int32))
        adm.next += C
        # NO per-chunk (or final-chunk) block here: every sync is deferred
        # to the step's single drain point (_drain_admissions), so the final
        # chunk's compute overlaps whatever the step dispatches after it.
        # compute_s so far holds enqueue time only; the drain stamps the
        # actual wait, keeping admit_compute_p95 honest under async dispatch
        adm.compute_s += time.perf_counter() - t0
        if adm.next in adm.mamba_register:
            self.pool.register_prefix(adm.slot, req.prompt,
                                      self.active_knobs, adm.next,
                                      mamba=self._mamba_snapshot(adm.slot))
        if adm.next < S:
            return
        # admission complete: register remaining boundaries (host
        # bookkeeping — needs no device sync) and park the admission at the
        # drain point; first-token sampling and slot activation happen there
        for b in adm.tail_register:
            self.pool.register_prefix(adm.slot, req.prompt,
                                      self.active_knobs, b)
        # lookup caps sharing at len(prompt)-1 tokens, so at least one chunk
        # always ran and produced the sampling logits
        assert adm.logits is not None
        del self._admissions[adm.slot]
        self._await_admit[adm.slot] = adm

    def _drain_admissions(self) -> None:
        """The admission half of the step's single drain point: block on
        each completed admission's final-chunk logits (the wait lands in
        ``admit_compute_s`` — the dispatch loop stamped only enqueue time),
        sample the first token, and hand the slot to the decode batch.
        Newly activated slots join the NEXT dispatch: the per-step path
        captures its row set before decoding, the megastep path merges them
        into the device carry via ``_inject_slots``."""
        if not self._await_admit:
            return
        freed = False
        for slot, adm in list(self._await_admit.items()):
            req = adm.req
            t0 = time.perf_counter()
            logits = np.asarray(adm.logits)          # <- the drain
            dt = time.perf_counter() - t0
            adm.compute_s += dt
            self.drain_block_s += dt
            del self._await_admit[slot]
            tok = int(self._sample_rows(logits, [req])[0])
            now = time.perf_counter()
            self.admit_latencies.append(adm.compute_s)
            self._token_lat.append(now - req.t_admit_start)  # TTFT (wall)
            req.t_admit = now                  # admission COMPLETION
            req.admit_compute_s = adm.compute_s
            req.out.append(tok)
            req.token_times.append(now)
            if len(req.out) >= req.max_new \
                    or (self.eos_id >= 0 and tok == self.eos_id):
                req.done = True                # 1-token request: no slot
                self._rngs.pop(req.uid, None)
                freed |= self._free_slot(slot)
                continue
            self.positions[slot] = len(req.prompt)
            self.cur_tokens[slot] = tok
            self._uids[slot] = req.uid
            self._pos_ub[slot] = len(req.prompt)
            self.slots[slot] = req
            if self.megastep_k:
                self._inject_slots.add(slot)
        if freed:
            self._push_blocks()

    def _admit(self) -> None:
        """Dense path: legacy synchronous admission (full chunked prefill
        into a fresh cache + slot insert inside one step)."""
        for i in range(self.batch_slots):
            while self.slots[i] is None and self.pending:
                req = self.pending[0]
                assert len(req.prompt) <= self.max_len, \
                    (len(req.prompt), self.max_len)
                t0 = time.perf_counter()
                req.t_admit_start = t0
                logits, rcaches = self._chunked_prefill(req.prompt)
                with self._ctx():
                    self.caches = self._insert(self.caches, rcaches, i)
                    if self._cache_sh is not None:
                        self.caches = jax.device_put(self.caches,
                                                     self._cache_sh)
                self.pending.popleft()
                tok = int(self._sample_rows(np.asarray(logits), [req])[0])
                now = time.perf_counter()
                self.admit_latencies.append(now - t0)
                self._token_lat.append(now - t0)   # TTFT sample
                req.t_admit = now                  # admission COMPLETION
                req.admit_compute_s = now - t0     # sync: compute == wall
                req.out.append(tok)
                req.token_times.append(now)
                if len(req.out) >= req.max_new or (
                        self.eos_id >= 0 and tok == self.eos_id):
                    req.done = True                # 1-token request: no slot
                    continue
                self.positions[i] = len(req.prompt)
                self.cur_tokens[i] = tok
                self.slots[i] = req

    # --------------------------------------------------------------- steps --

    def _call_decode(self, exe, args, cache_idx: int):
        """Dispatch a decode/megastep executable with honest collective-
        failure retry under donation: the call CONSUMES the caches argument
        when donation is on, so a queued injected failure snapshots the
        pre-step caches first and the retry re-issues from the snapshot —
        the semantics stay "results discarded uncommitted, step re-run",
        bounded by the injected count."""
        while True:
            retry = self._collective_failures > 0
            if retry and self.donate:
                safe = jax.tree.map(jnp.copy, args[cache_idx])
            out = exe(*args)
            if not retry:
                return out
            self._collective_failures -= 1
            self.stats["collective_retries"] += 1
            if self.donate:
                args = args[:cache_idx] + (safe,) + args[cache_idx + 1:]

    def _megastep_exe(self, k: int):
        """The fused K-step executable for the ACTIVE variant, lowered
        lazily per (variant, K) and cached — the QoS budget only ever picks
        K from {1, megastep_k}, so at most two executables per variant.
        Cleared (and re-donated on rebuild) by ``_rehome``."""
        key = (self._active, k)
        exe = self._megasteps.get(key)
        if exe is not None:
            return exe
        step = step_mod.make_paged_megastep(
            self.cfg, self.active_knobs, k=k, temperature=self.temperature,
            seed=self.seed, eos_id=self.eos_id, mesh=self.mesh,
            use_kernel=self.use_kernel, dynamic_scatter=self.mesh is None,
            interpret=self.kernel_interpret)
        kw = dict(donate_argnums=(7,)) if self.donate else {}
        if self.mesh is None:
            exe = jax.jit(step, **kw)
        else:
            from repro.dist import sharding as dist_sharding
            in_sh, out_sh = dist_sharding.megastep_shardings(
                self._param_sh, self._cache_sh)
            exe = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                          **kw)
        self._megasteps[key] = exe
        return exe

    def _dispatch_megastep(self) -> Optional[dict]:
        """Dispatch ONE fused K-step decode over the live slots without
        waiting on it (async pipeline): pre-map every page the in-scan
        cursor advance can touch, merge newly activated slots into the
        device carry, and return the flight record the drain consumes.

        The carry (cur/pos/alive/draws/budget) chains device-side between
        dispatches — rows die IN-SCAN on EOS/budget, so the device alive
        mask already agrees with the host's post-drain view and only slot
        (re)activations need injecting (``_inject_slots``). Returns None
        when no slot is decoding."""
        rows = [i for i in range(self.batch_slots)
                if self.slots[i] is not None]
        if not rows:
            # nothing alive: the device carry is stale by construction (the
            # next activation cold-starts from the host mirrors) — drop it
            # so idle engines hold no donated-cache chain
            self._carry = None
            return None
        k = self._megastep_budget()
        # never scan past the longest remaining budget: a row with one
        # token left must not pay a K-step full-batch scan
        k = max(1, min(k, max(self.slots[i].max_new - len(self.slots[i].out)
                              for i in rows)))
        dirty = False
        for i in rows:
            req = self.slots[i]
            # exclusive bound on write positions this row can ever need:
            # decode writes KV at S .. S+max_new-2 (the first of max_new
            # tokens was sampled at admission). _pos_ub ratchets forward by
            # k per dispatch — the host's mirror of the in-scan cursor,
            # conservative while a prior megastep is still in flight
            cap = len(req.prompt) + req.max_new - 1
            ub = min(int(self._pos_ub[i]) + k, cap)
            dirty |= self.pool.ensure_decode_range(
                i, int(self.positions[i]), ub)
            self._pos_ub[i] = ub
        if dirty:
            self._push_blocks()
        t0 = time.perf_counter()
        B = self.batch_slots
        alive_host = np.array([s is not None for s in self.slots])
        with self._ctx():
            if self._carry is None:
                # cold start (first dispatch / post-rehome): the host
                # mirrors are authoritative
                draws = jnp.asarray(np.array(
                    [len(self.slots[i].out) if alive_host[i] else 0
                     for i in range(B)], np.int32))
                budget = jnp.asarray(np.array(
                    [self.slots[i].max_new - len(self.slots[i].out)
                     if alive_host[i] else 0 for i in range(B)], np.int32))
                cur = jnp.asarray(self.cur_tokens)
                pos = jnp.asarray(self.positions)
                alive = jnp.asarray(alive_host)
            else:
                cur, pos, alive, draws, budget = self._carry
                if self._inject_slots:
                    m = np.zeros(B, bool)
                    inj_draws = np.zeros(B, np.int32)
                    inj_budget = np.zeros(B, np.int32)
                    for i in self._inject_slots:
                        req = self.slots[i]
                        m[i] = True
                        inj_draws[i] = len(req.out)
                        inj_budget[i] = req.max_new - len(req.out)
                    mj = jnp.asarray(m)
                    cur = jnp.where(mj, jnp.asarray(self.cur_tokens), cur)
                    pos = jnp.where(mj, jnp.asarray(self.positions), pos)
                    alive = jnp.where(mj, True, alive)
                    draws = jnp.where(mj, jnp.asarray(inj_draws), draws)
                    budget = jnp.where(mj, jnp.asarray(inj_budget), budget)
            args = (self.params, cur, pos, alive, jnp.asarray(self._uids),
                    draws, budget, self.caches)
            toks, cur, pos, alive, draws, budget, new_caches = \
                self._call_decode(self._megastep_exe(k), args, 7)
            self.caches = new_caches
            self._carry = (cur, pos, alive, draws, budget)
        self._inject_slots.clear()
        self.decode_dispatches += 1
        return dict(toks=toks, rows=[(i, self.slots[i]) for i in rows],
                    k=k, t0=t0)

    def _drain_megastep(self, flight: dict) -> None:
        """THE decode drain point: one transfer surfaces up to K tokens and
        the stop flags (the -1 sentinel; vocab ids are >= 0) per row.
        Per-token times interpolate linearly across the megastep wall — the
        same per-megastep -> per-token attribution the QoS monitor applies
        (``LatencyMonitor.record_megastep``). Finished rows free their
        slot/pages here; banded archs release window-dead pages."""
        t0 = time.perf_counter()
        toks = np.asarray(flight["toks"])
        now = time.perf_counter()
        self.drain_block_s += now - t0
        wall = now - flight["t0"]
        self.step_latencies.append(wall)
        for entry in self._recovering:
            # recovery = event application -> first COMPLETED megastep on
            # the re-homed mesh (compile time of the cutover included)
            entry["recovery_steps"] = \
                len(self.step_latencies) - entry["step_index"]
            entry["recovery_s"] = now - entry.pop("_t_rehome")
        self._recovering.clear()
        freed = False
        emitted: List[int] = []
        for i, req in flight["rows"]:
            if req.done:
                continue   # died in an earlier flight; this row is all -1
            n = 0
            for t in toks[i]:
                if t < 0:
                    break  # row died in-scan: EOS or budget exhausted
                n += 1
                req.out.append(int(t))
                self.cur_tokens[i] = int(t)
                self.positions[i] += 1
            if n:
                emitted.append(n)
                self.row_dispatches += 1
                self.row_tokens += n
                for j in range(n):
                    req.token_times.append(
                        flight["t0"] + wall * (j + 1) / n)
            if len(req.out) >= req.max_new or (
                    self.eos_id >= 0 and req.out
                    and req.out[-1] == self.eos_id):
                req.done = True
                self.slots[i] = None        # slot freed: continuous batch
                self._rngs.pop(req.uid, None)
                freed |= self._free_slot(i)
            elif self._window_free:
                # banded arch: pages that fell out of every layer's window
                # are dead — return them so long decodes hold occupancy flat
                freed |= self.pool.release_window_pages(
                    i, int(self.positions[i]) - self._window_free)
        if freed:
            self._push_blocks()
        if self.runtime is not None and emitted:
            self.runtime.monitor.record_megastep(wall, emitted)

    def _drain_pipeline(self) -> None:
        """Flush the async double-buffer before state surgery (elastic
        re-home): drain the in-flight megastep so its tokens land and its
        donated-cache chain settles, and invalidate the device carry — the
        next dispatch cold-starts from the host mirrors."""
        if self._inflight is not None:
            self._drain_megastep(self._inflight)
            self._inflight = None
        self._carry = None

    def _megastep_round(self) -> None:
        """One engine step in megastep mode — the async double-buffered
        host pipeline: advance admissions, dispatch megastep N+1, THEN
        drain megastep N (the device never idles waiting for the host to
        process tokens), drain completed admissions, tick control. The ONE
        explicit drain pair (``_drain_megastep`` + ``_drain_admissions``)
        replaces the per-step path's scattered blocking calls.
        ``sync_timing`` drains each dispatch in its own round instead — no
        overlap, but per-token stamps measure compute, not enqueue."""
        prev, self._inflight = self._inflight, None
        self._advance_admissions()
        flight = self._dispatch_megastep()
        if prev is not None:
            self._drain_megastep(prev)    # dispatch order == drain order
        if flight is not None and self.sync_timing:
            self._drain_megastep(flight)
            flight = None
        self._inflight = flight
        self._drain_admissions()
        self.pool.replenish()
        self._control_tick()

    def step(self) -> None:
        """One engine step. Megastep (``megastep_k`` > 0): one async
        double-buffered pipeline round (``_megastep_round``). Paged
        per-step: run the continuous-batching admission phase (open
        admissions on every free slot, advance them under the QoS chunk
        budget), dispatch one decode for every active slot (admitting slots
        ride along inactive, their writes masked), then drain admissions
        and the decode at the step's single drain point — a long prompt
        never stalls the decoders for more than the chunk budget. Dense:
        legacy synchronous admission, then decode. All tick the Pliant
        control loop at the step boundary."""
        self.step_count += 1
        self._process_capacity()   # deadline-reached capacity events cut
        self._expire_pending()     # over first, at the step boundary
        if self.paged and self.megastep_k > 0:
            self._megastep_round()
            return
        if self.paged:
            self._advance_admissions()
        else:
            self._admit()
        # the decode row set is FIXED here: slots activated at this step's
        # admission drain join the next step's decode
        rows = [i for i, req in enumerate(self.slots) if req is not None]
        if not rows:
            if self.paged:
                self._drain_admissions()  # no decode to overlap — drain now
                self.pool.replenish()     # keep headroom between steps
            self._control_tick()       # flush TTFT samples of 1-token admits
            return
        if self.paged:
            # map each live slot's write page before the step scatters to it
            # (live growth bypasses the reclaim limit — see serve.pages).
            # Grouped admission already reserved these pages, so this is a
            # no-op except for banded archs (which skip the reservation)
            dirty = False
            for i in rows:
                dirty |= self.pool.ensure_decode_page(
                    i, int(self.positions[i]))
            if dirty:
                self._push_blocks()
        t0 = time.perf_counter()
        with self._ctx():
            toks = jnp.asarray(self.cur_tokens)[:, None]
            pos = jnp.asarray(self.positions)
            if self.paged:
                act = jnp.asarray(
                    np.array([s is not None for s in self.slots]))
                args = (self.params, toks, pos, act, self.caches)
                cidx = 4
            else:
                args = (self.params, toks, pos, self.caches)
                cidx = 3
            out, new_caches = self._call_decode(
                self._decodes[self._active], args, cidx)
            self.caches = new_caches
            self.decode_dispatches += 1
            if self.paged:
                # the step's single drain point: admission chunks were
                # dispatched BEFORE the decode, so draining them here never
                # waits on the decode — their compute overlapped its
                # dispatch (satellite of the megastep pipeline)
                self._drain_admissions()
            # fused greedy: ``out`` is (B,) sampled token ids — B*4 bytes
            # off-device per step instead of the (B, V) logits matrix
            tb = time.perf_counter()
            out = np.asarray(out)
            self.drain_block_s += time.perf_counter() - tb
        dt = time.perf_counter() - t0
        self.step_latencies.append(dt)
        for entry in self._recovering:
            # recovery = event application -> first COMPLETED decode step on
            # the re-homed mesh (compile time of the cutover step included)
            entry["recovery_steps"] = \
                len(self.step_latencies) - entry["step_index"]
            entry["recovery_s"] = time.perf_counter() - entry.pop("_t_rehome")
        self._recovering.clear()
        now = time.perf_counter()
        if self._fused_sample:
            nxt_tokens = out[rows]
        else:
            nxt_tokens = self._sample_rows(
                out[rows], [self.slots[i] for i in rows])
        freed = False
        for i, nxt in zip(rows, nxt_tokens):
            req = self.slots[i]
            nxt = int(nxt)
            self.positions[i] += 1
            req.out.append(nxt)
            req.token_times.append(now)
            self.cur_tokens[i] = nxt
            self.row_dispatches += 1
            self.row_tokens += 1
            if len(req.out) >= req.max_new or (
                    self.eos_id >= 0 and nxt == self.eos_id):
                req.done = True
                self.slots[i] = None            # slot freed: continuous batch
                self._rngs.pop(req.uid, None)
                if self.paged:
                    freed |= self._free_slot(i)
            elif self._window_free:
                # banded arch: pages that fell out of every layer's window
                # are dead — return them so long decodes hold occupancy flat
                freed |= self.pool.release_window_pages(
                    i, int(self.positions[i]) - self._window_free)
        if freed:
            self._push_blocks()
        if self.paged:
            self.pool.replenish()      # watermark top-up, off the admission
        self._token_lat.extend([dt] * len(rows))   # path (between steps)
        self._control_tick()

    def _control_tick(self) -> None:
        """Monitor -> controller -> actuator at the step boundary. Variant
        swaps are deferred while an admission is in flight: a mid-prompt
        knob change would mix admission executables (and prefix tags)
        within one request."""
        if self.runtime is None:
            self._token_lat.clear()
            return
        if self._token_lat:
            self.runtime.monitor.record_many(self._token_lat)
            self._token_lat.clear()
        self.runtime.maybe_decide()
        if self._bound:
            # actuation arrived via the tenant adapter (request_variant);
            # apply any swap deferred by an in-flight admission
            self._apply_pending_variant()
        elif (self.runtime.active_variant != self._active
                and not self._admissions and not self._await_admit):
            # runtime owned by someone else (no tenant binding): follow its
            # decision state by polling, as before the tenant protocol
            self.set_variant(self.runtime.active_variant)

    @property
    def idle(self) -> bool:
        """Nothing to do: empty queue, no in-flight background admissions,
        no active slots. Drivers must check this (not just pending/slots)
        before parking — a paged admission spans multiple steps."""
        return (not self.pending and not self._admissions
                and not self._await_admit and self._inflight is None
                and all(s is None for s in self.slots))

    def run(self, max_steps: int = 0) -> None:
        """Step until idle. ``max_steps`` (0 = auto) is a runaway backstop,
        sized to the queued work: stall-free admission spends one step per
        prefill CHUNK, so the old flat cap silently truncated long-prompt
        workloads mid-flight. Hitting the cap non-idle raises — callers'
        stats must never summarize a silently truncated run."""
        if not max_steps:
            chunks = sum(-(-len(r.prompt) // max(self.prefill_chunk, 1)) + 2
                         for r in self.pending)
            decodes = sum(r.max_new for r in self.pending)
            max_steps = 10_000 + 2 * (chunks + decodes)
        steps = 0
        while not self.idle and steps < max_steps:
            self.step()
            steps += 1
        if not self.idle:
            raise RuntimeError(
                f"engine not idle after {steps} steps: "
                f"{len(self.pending)} pending, "
                f"{len(self._admissions)} admissions in flight, "
                f"{sum(s is not None for s in self.slots)} active slots")
