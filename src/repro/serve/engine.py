"""Batched serving engine with continuous batching (slot-based) and a
unified ragged prefill+decode dispatch (merge-mode serving).

A fixed pool of ``batch_slots`` cache slots; requests are admitted into free
slots and every engine tick advances work with one jitted fused dispatch.
Two dispatch shapes exist, chosen per tick from the workload mix — the
temporal analogue of Spatzformer's split/merge reconfiguration:

* **packed tick** (merge mode — any admission in flight): a flat
  ``[T_bucket]`` token batch packs up to ``prefill_budget`` prompt tokens
  from the admitting requests (Sarathi-style chunked prefill) through
  ``LM.packed_step`` → the ragged varlen attention kernel with per-token
  ``(slot, position)`` descriptors; new K/V are scattered at (slot, pos) in
  one fused O(T) write — no B=1 prefill, no full-cache insert copy, no
  blocking logits transfer + host sample per admission (a completing
  chunk's first token is sampled on device from its final prompt row). In
  the SAME loop iteration every decoding slot advances through a fused
  decode chunk, so decode NEVER stalls behind an admission. A handful of T
  buckets replaces the per-prompt-length prefill compile zoo.
* **decode chunk** (split mode — steady state, no admission work): decode +
  device-side sampling + the per-slot ``cur_len`` advance fused and
  scanned ``k`` steps deep, where ``k`` (bucketed to powers of two up to
  ``max_chunk``) is the largest chunk in which no slot can finish —
  count-based termination depends only on counts, so the host knows ``k``
  in advance and chunking is output-invariant. A steady-state chunk ships
  zero host arrays to the device, so merge-mode reconfigurability costs
  the split-mode steady state nothing (the paper's C3 parity).

Sampling is request-level configuration (:mod:`repro.serve.sampling`):
every request carries a frozen :class:`SamplingParams` (temperature,
top-k, top-p, seed, max_new, stop ids, logit bias); the per-slot parameter
rows live device-resident and are re-uploaded only on slot-change events,
and each dispatch runs one of a finite zoo of compiled sampler variants
(``smode``) chosen per tick by a host ``if`` over the active slots. Every
draw is keyed ``fold_in(key(request_seed), position)`` — no shared PRNG
chain — so seeded streams are reproducible across chunk sizes, across the
legacy/unified engines, and across cluster modes, and a neighbour slot
being admitted or cancelled never perturbs anyone else's tokens. The
all-greedy fast path (smode 0) skips threefry/bias/sort entirely and is
bit-identical to the pre-SamplingParams engine.

Speculative decoding (``speculate=``, :mod:`repro.serve.speculate`) rides
the same packed ragged dispatch: a host-side drafter (n-gram prompt
lookup, or a small draft model) proposes up to ``k`` tokens per decoding
slot, ONE packed dispatch scores all ``k+1`` positions per slot
(scattering the proposals' K/V at their hypothetical positions), and the
seeded fold_in sampler draws the target token at every position in the
same dispatch. Because every draw is a pure function of (context, seed,
position), acceptance is plain exact-match — a speculated stream is
bit-identical to its non-speculated twin BY CONSTRUCTION, not merely in
distribution. Rejected positions need no rollback: host bookkeeping never
advanced past the committed prefix, and stale K/V beyond ``cur_len`` is
masked by the position predicate until overwritten (the paged engine
releases nothing — block tables reserve the worst case at admission).

Request lifecycle: :meth:`ServeEngine.submit` returns a
:class:`RequestHandle` — an incremental token iterator with ``cancel()``;
``run()`` is rebuilt on the same per-iteration step machinery
(:meth:`ServeEngine.step`). Stop tokens are detected at harvest time (the
host-side value crossing that already exists), so count-based chunk
sizing — and with it chunking invariance — survives value-dependent
termination at the cost of at most one discarded in-flight chunk.

Shared hot-path structure:

* every host→device crossing (params/cache placement, tick state, the
  per-tick staging uploads, program compilation) goes through a pluggable
  :mod:`repro.serve.backend` — the same loop serves the default device, a
  pinned split-mode replica, or a tensor-parallel mesh (merge-mode
  cluster serving, :mod:`repro.serve.cluster`);
* tick state (last tokens, cur_len) is device-resident; host bookkeeping
  tracks counts only and harvests tick t-1's token values while tick t
  computes (count-based termination never waits on token values);
* the decode cache is donated through every dispatch — the engine never
  holds two copies of the KV cache;
* MLA archs ride the same packed dispatch with a latent cache: one
  compressed ``c_kv`` row (+ decoupled-RoPE key) per position instead of
  per-head K/V, attention as the latent-MQA specialization of the ragged
  kernel, the scatter writing one latent row per token;
* SSM archs serve with NO positional cache at all: per-slot
  ``(conv_state, ssd_state)``, chunked prefill as single-slot
  state-passing scans through the same T-bucket ladder, constant
  resident bytes (paged/quantized/speculate are typed refusals);
* hybrid archs (attention + SSM interleaved) keep the legacy
  per-request tier, flagged by a one-time RuntimeWarning per process.
"""

from __future__ import annotations

import threading
import time
import warnings
from collections import deque
from dataclasses import dataclass, field
from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.model import LM
from repro.models.quant import quantize_params
from repro.serve.backend import PlacementBackend, resolve_backend
from repro.serve.kv_pool import BlockPool, blocks_for
from repro.serve.prefix_cache import RadixPrefixCache
from repro.serve.sampling import (
    SMODE_GREEDY,
    SamplingParams,
    bias_row,
    fused_sample,
    param_rows,
    spec_verify,
)
from repro.serve.speculate import SpeculateConfig, build_drafter


class AdmissionRejected(ValueError):
    """Typed submit-time rejection: the request was never queued.

    ``reason`` distinguishes the four admission outcomes so clients and
    the cluster router can react differently to each:

    * ``"infeasible"``   — could never be served (e.g. needs more KV
      blocks than the paged pool holds); retrying is pointless.
    * ``"shed_deadline"`` — predicted TTFT exceeds the request's
      ``deadline_s``; admitting it would only make it miss late.
    * ``"rate_limited"``  — the tenant's token bucket is empty; retry
      after backoff.
    * ``"queue_full"``    — bounded-queue backpressure; retry after
      backoff or raise the request's priority.

    Subclasses :class:`ValueError` so pre-existing callers that caught
    the bare infeasible-paged-request raise keep working.
    """

    REASONS = ("infeasible", "shed_deadline", "rate_limited", "queue_full")

    def __init__(self, reason: str, detail: str = "") -> None:
        assert reason in self.REASONS, reason
        self.reason = reason
        super().__init__(f"{reason}: {detail}" if detail else reason)


@dataclass(eq=False)
class Request:
    """One serving request. Identity-based equality/hash: a Request is a
    live lifecycle object (queues, slot tables, handle maps key on it),
    not a value.

    Sampling/termination configuration lives in ``params``
    (:class:`SamplingParams`). The bare ``max_new=``/``temperature=``
    kwargs are the pre-SamplingParams surface, kept as deprecation shims:
    they build (and stay mirrored from) ``params`` so old callers and the
    router's cost model keep working."""

    rid: int
    prompt: np.ndarray  # [S] int32
    max_new: Optional[int] = None  # deprecated: use params=SamplingParams(...)
    temperature: Optional[float] = None  # deprecated: use params=...
    params: Optional[SamplingParams] = None
    tenant: Optional[str] = None  # cluster router affinity key (optional)
    model: Optional[str] = None  # heterogeneous cluster: pin to a named model
    deadline_s: Optional[float] = None  # TTFT budget: shed if predicted to miss
    generated: list[int] = field(default_factory=list)
    n_generated: int = 0  # tokens sampled so far (values may still be in flight)
    submitted_at: float = 0.0
    first_token_at: Optional[float] = None
    done_at: Optional[float] = None
    finish_reason: Optional[str] = None  # "length"|"stop"|"cancelled"|"rejected"
    reject_reason: Optional[str] = None  # AdmissionRejected.reason when rejected

    def __post_init__(self):
        explicit = (
            self.params is not None
            or self.max_new is not None
            or self.temperature is not None
        )
        if self.params is None:
            if self.max_new is not None or self.temperature is not None:
                warnings.warn(
                    "Request(max_new=..., temperature=...) is deprecated; "
                    "pass params=SamplingParams(max_new=..., temperature=...)",
                    DeprecationWarning,
                    stacklevel=3,
                )
            self.params = SamplingParams(
                temperature=self.temperature if self.temperature is not None else 0.0,
                max_new=self.max_new if self.max_new is not None else 16,
            )
        elif self.max_new is not None or self.temperature is not None:
            raise ValueError("pass either params= or the legacy kwargs, not both")
        # whether the caller configured sampling at all: a cluster's
        # per-tenant default only fills requests that did not
        self._explicit_params = explicit
        self._sync_mirrors()

    def _sync_mirrors(self) -> None:
        self.max_new = self.params.max_new
        self.temperature = self.params.temperature

    def apply_default_params(self, params: SamplingParams) -> None:
        """Fill in a default ``SamplingParams`` (e.g. a cluster's per-tenant
        default) — a no-op when the caller configured the request."""
        if self._explicit_params:
            return
        self.params = params
        self._explicit_params = True
        self._sync_mirrors()

    @property
    def complete(self) -> bool:
        """Finished AND every token value harvested to the host."""
        return (
            self.finish_reason is not None
            and len(self.generated) >= self.n_generated
        )


class RequestHandle:
    """Streaming view of one submitted request: an incremental token
    iterator plus ``cancel()``. Tokens become visible as the engine
    harvests them (one dispatch behind the device, by design); iterating
    from the submitting thread *drives* the engine (``step()``) when
    nothing else is, and politely polls when a controller thread (cluster
    split mode) owns the serving loop."""

    def __init__(self, request: Request, owner) -> None:
        self.request = request
        self._owner = owner  # ServeEngine or ServeCluster
        self.replica = None  # split-mode routing target (set by ServeCluster)

    @property
    def rid(self) -> int:
        return self.request.rid

    @property
    def params(self) -> SamplingParams:
        return self.request.params

    @property
    def done(self) -> bool:
        return self.request.complete

    @property
    def finish_reason(self) -> Optional[str]:
        return self.request.finish_reason

    def cancel(self) -> None:
        """Abort the request: dequeue it if waiting, free its slot if
        decoding. In-flight token values are discarded; no other slot's
        output is perturbed (sampling keys are per-request, never shared)."""
        self._owner.cancel(self.request)

    def tokens(self) -> Iterator[int]:
        """Yield generated token ids incrementally until the request
        finishes (length/stop) or is cancelled."""
        i = 0
        while True:
            if i < len(self.request.generated):
                yield self.request.generated[i]
                i += 1
            elif self.done:
                # completion may have been a side effect of a batch-mate's
                # streaming — give the owner its bookkeeping hook (the
                # cluster prunes its request→engine ownership map here)
                done_hook = getattr(self._owner, "_handle_done", None)
                if done_hook is not None:
                    done_hook(self.request)
                return
            else:
                self._owner._handle_pump(self.request)

    __iter__ = tokens

    def result(self) -> list[int]:
        """Block (driving the engine if needed) until complete; returns the
        full generated token list."""
        for _ in self.tokens():
            pass
        return self.request.generated


def percentile(xs: list[float], q: float) -> float:
    """Latency percentile with the empty-sample sentinel (0.0) — shared by
    ServeStats and the cluster's ClusterStats so the two never diverge."""
    return float(np.percentile(np.asarray(xs), q)) if xs else 0.0


@dataclass
class ServeStats:
    total_tokens: int = 0
    total_requests: int = 0
    cancelled: int = 0  # requests aborted via handle.cancel()
    wall_seconds: float = 0.0
    ticks: int = 0
    prefill_compiles: int = 0
    # speculative decoding telemetry (0 unless the engine speculates):
    # proposed = draft tokens dispatched to verify, accepted = drafts that
    # matched their seeded target draw (the bonus token is neither)
    spec_proposed: int = 0
    spec_accepted: int = 0
    spec_ticks: int = 0
    # backpressure / robustness telemetry: queue high-water mark and paged
    # allocation failures are engine-level (filled by run()); shed /
    # rejected / re-homed counts only become nonzero at the cluster layer,
    # which owns admission control and failure recovery
    queue_peak: int = 0
    alloc_failures: int = 0
    # peak resident KV bytes observed over the run, dtype-aware (an int8
    # cache reports ~4x fewer bytes than f32 for the same positions):
    # dense = the constant cache allocation, paged = peak used_blocks x
    # measured bytes_per_block — the number capacity planning should read
    kv_bytes_resident: int = 0
    shed: int = 0  # deadline-based load shedding (shed_deadline)
    rejected: int = 0  # rate_limited + queue_full rejections
    rehomed: int = 0  # live requests moved off a dead replica
    # per-request latency samples for the requests finished in this run:
    # TTFT = first token available - submitted; TPOT = mean inter-token time
    ttfts: list[float] = field(default_factory=list)
    tpots: list[float] = field(default_factory=list)

    @property
    def tokens_per_sec(self) -> float:
        return self.total_tokens / max(self.wall_seconds, 1e-9)

    @property
    def spec_acceptance(self) -> float:
        """Fraction of dispatched draft tokens that matched their target."""
        return self.spec_accepted / max(self.spec_proposed, 1)

    @property
    def ttft_p50(self) -> float:
        return percentile(self.ttfts, 50)

    @property
    def ttft_p99(self) -> float:
        return percentile(self.ttfts, 99)

    @property
    def tpot_p50(self) -> float:
        return percentile(self.tpots, 50)

    @property
    def tpot_p99(self) -> float:
        return percentile(self.tpots, 99)


def _norm_kv_dtype(kv_dtype):
    """Engine-level kv_dtype normalization: ``None`` means the plain
    (scale-less) cache; ``"f32"``/``"float32"`` opts into the quantized-row
    machinery with an f32 store and identity scales (the bit-identity test
    lane); ``"fp8"``/``"float8_e4m3"`` stores rows as float8_e4m3fn (same
    per-row scales, wider dynamic range than int8 at the same byte cost);
    anything else must resolve to int8."""
    if kv_dtype is None:
        return None
    if isinstance(kv_dtype, str):
        if kv_dtype in ("f32", "float32"):
            return jnp.float32
        if kv_dtype in ("f8", "fp8", "float8", "float8_e4m3", "float8_e4m3fn"):
            return jnp.float8_e4m3fn
        kv_dtype = "int8" if kv_dtype == "i8" else kv_dtype
    try:
        dt = jnp.dtype(kv_dtype)
    except TypeError as e:
        raise ValueError(f"unsupported kv_dtype: {kv_dtype!r}") from e
    if dt not in (
        jnp.dtype(jnp.float32),
        jnp.dtype(jnp.int8),
        jnp.dtype(jnp.float8_e4m3fn),
    ):
        raise ValueError(f"unsupported kv_dtype: {kv_dtype!r}")
    return dt


def _bucket_len(s: int, max_len: int) -> int:
    """Next power of two ≥ s, capped at max_len (prefill compile buckets)."""
    b = 1
    while b < s:
        b *= 2
    return min(b, max_len) if b > s else b


# packed-tick size buckets: a 1.5x ladder keeps padding waste ≤ ~33% while a
# handful of compiled T variants covers every workload mix
_T_BUCKETS = (8, 16, 24, 32, 48, 64, 96, 128)

# max admitting slots per pack (the P in the sub-cache gather); admissions
# beyond it join the next tick's pack
_PACK_WIDTH = 2

# family tags already warned about riding the legacy tier (once per process)
_LEGACY_WARNED: set = set()


def _warn_legacy_tier(tag: str) -> None:
    """One-time heads-up that a family serves on the slow legacy tier:
    blocking B=1 prefill + full-cache insert + host-side first-token sample
    per admission, no packed ragged dispatch. Correct, but every admission
    stalls all decode slots."""
    if tag in _LEGACY_WARNED:
        return
    _LEGACY_WARNED.add(tag)
    warnings.warn(
        f"family {tag!r} has no packed path; serving on the legacy "
        "prefill+insert tier (each admission blocks the decode slots)",
        RuntimeWarning,
        stacklevel=3,
    )


def _bucket_tokens(t: int) -> int:
    for b in _T_BUCKETS:
        if t <= b:
            return b
    b = _T_BUCKETS[-1]
    while b < t:
        b *= 2
    return b


class ServeEngine:
    def __init__(
        self,
        model: LM,
        params,
        *,
        batch_slots: int = 4,
        max_len: int = 256,
        seed: int = 0,
        unified: Optional[bool] = None,
        prefill_budget: int = 64,
        max_chunk: int = 8,
        backend: Optional[PlacementBackend] = None,
        kv_block_size: Optional[int] = None,
        num_blocks: Optional[int] = None,
        prefix_cache: bool = False,
        speculate=None,
        kv_dtype=None,
        weight_dtype=None,
    ):
        self.model = model
        # EVERY host→device crossing goes through the backend: the engine
        # itself is placement-agnostic (single device, pinned replica
        # device, or tensor-parallel mesh — see serve/backend.py)
        self.backend = resolve_backend(backend)
        # quantized serving knobs (both opt-in; None = today's exact path):
        # * kv_dtype: "int8" stores K/V rows quantized with per-(pos, head)
        #   f32 scales resident in the cache pytree, dequantized inside the
        #   attention kernels; "f32" keeps the full scale machinery but an
        #   f32 store (identity scales) — the bit-identity test lane. None
        #   is the plain cache: no scale leaves, byte-identical to before.
        # * weight_dtype: "int8" rewrites eligible stacked matmul weights
        #   to {"q8", "scale"} sub-dicts (repro.models.quant); consuming
        #   einsums dequantize per layer via the qweight read-through.
        self.kv_dtype = _norm_kv_dtype(kv_dtype)
        self.weight_dtype = weight_dtype
        self.quant_kv = self.kv_dtype is not None
        self.params = self.backend.put_params(
            model, quantize_params(params, weight_dtype)
        )
        self.B = batch_slots
        self.max_len = max_len
        self.seed = seed
        # unified ragged dispatch covers positional-KV attention (dense/
        # moe), the MLA compressed-latent cache, and single-slot SSM state
        # chunks; only hybrid (attention+SSM interleaved per block) keeps
        # the legacy prefill+insert path
        self.unified = model.supports_packed if unified is None else unified
        if self.unified and not model.supports_packed:
            raise ValueError(
                f"family {model.family_tag!r} has no packed path "
                "(pass unified=False to serve it on the legacy tier)"
            )
        if not self.unified:
            _warn_legacy_tier(model.family_tag)
        # recurrent-state packs are single-stream: ONE slot per pack, so
        # the whole [T] chunk is a contiguous run of that slot's positions
        # and the state-passing chunk scan applies verbatim. Attention
        # packs keep the multi-slot width.
        self._pack_width = 1 if model.cfg.family == "ssm" else _PACK_WIDTH
        # families whose admissions must ALWAYS ride the chunked packed
        # tier (never the fused prefill+insert dispatch): quantized KV
        # (the packed scatter is the one write path that quantizes rows)
        # and SSM (exact-length B=1 prefill would compile per prompt
        # length; the chunk scan reuses the T-bucket ladder instead)
        self._chunk_only_admit = (
            self.kv_dtype is not None or model.cfg.family == "ssm"
        )
        self.prefill_budget = max(int(prefill_budget), 1)
        self.max_chunk = max(int(max_chunk), 1)
        if self.quant_kv and not self.unified:
            # model.prefill builds a scale-less B=1 cache — the legacy
            # insert path cannot carry scales. Quantized KV rides the
            # packed/chunked tier exclusively (see _admit_unified).
            raise ValueError("kv_dtype requires the unified packed engine")
        # speculative decoding (serve/speculate.py): a drafter proposes up
        # to spec_k tokens per decoding slot and ONE packed verify dispatch
        # scores every (slot, offset) row; accepted prefixes commit through
        # the normal harvest path. `speculate` accepts a CLI-style string
        # ("ngram" | "draft[:<arch>]"), a SpeculateConfig, or a bound
        # Drafter instance (anything with .propose).
        self.spec: Optional[SpeculateConfig] = None
        self.drafter = None
        if speculate not in (None, False, "off"):
            if not self.unified:
                raise ValueError(
                    "speculative decoding needs the unified packed dispatch"
                )
            if model.cfg.family == "ssm":
                # attention verify rows are free to reject (stale K/V past
                # cur_len is masked); a recurrent state has no position
                # axis, so rejected draft rows would need a state rollback
                raise ValueError(
                    f"family {model.family_tag!r} cannot speculate: "
                    "rejected drafts would need recurrent-state rollback"
                )
            if hasattr(speculate, "propose"):  # a pre-built Drafter
                self.spec = SpeculateConfig(
                    mode="draft" if getattr(speculate, "name", "") == "draft"
                    else "ngram"
                )
                self.drafter = speculate
            else:
                self.spec = SpeculateConfig.coerce(speculate)
                self.drafter = build_drafter(self.spec, model, params)
            self.spec_k = min(int(self.spec.k), max(max_len // 2, 1))
            self.drafter.setup(
                self.backend, batch_slots, max_len, model.cfg.vocab_size
            )
            # per-slot acceptance EWMA drives the adaptive depth (optimistic
            # start: a fresh slot tries the full depth, misses shrink it)
            self._spec_ewma = np.ones(batch_slots)
            self._spec_shapes: set[tuple[int, int]] = set()
        # block-paged KV mode (kv_block_size set): the dense [B, S_max]
        # cache becomes a [num_blocks, block_size] pool + per-slot block
        # tables (serve/kv_pool.py). Opt-in — the dense path below stays
        # byte-identical for existing callers (and the gated steady bench).
        self.kv_block_size = int(kv_block_size) if kv_block_size else 0
        self.paged = bool(self.kv_block_size)
        if self.paged:
            if not self.unified:
                raise ValueError("paged KV serving requires the unified engine")
            if max_len % self.kv_block_size:
                raise ValueError(
                    f"max_len={max_len} must be a multiple of "
                    f"kv_block_size={self.kv_block_size}"
                )
            self._maxb = max_len // self.kv_block_size  # table width
            # default pool = byte parity with the dense cache; capacity
            # deployments pass more slots than the pool could worst-case
            # hold and let admission wait on pool pressure instead
            self.num_blocks = (
                int(num_blocks) if num_blocks else batch_slots * self._maxb
            )
            self.pool = BlockPool(self.num_blocks, self.kv_block_size)
            self.prefix = (
                RadixPrefixCache(self.pool, self.kv_block_size)
                if prefix_cache else None
            )
            self.cache = self.backend.put_cache(
                model,
                model.init_kv_pool(
                    self.num_blocks, self.kv_block_size, kv_dtype=self.kv_dtype
                ),
            )
            # dtype-aware byte accounting: measure ONE block's HBM weight
            # from the live pool leaves (K + V payloads + scale planes over
            # all L layers) — never assume blocks are f32
            self.pool.bytes_per_block = sum(
                int(leaf.nbytes) // self.num_blocks
                for leaf in jax.tree.leaves(self.cache)
            )
            # per-slot block lists (host) + the [B, max_blocks] device
            # table; unallocated entries hold the out-of-range sentinel
            # num_blocks, so their scatters drop and their tiles are dead
            self._slot_blocks: list[list[int]] = [[] for _ in range(batch_slots)]
            self._btab_h = np.full(
                (batch_slots, self._maxb), self.num_blocks, np.int32
            )
            self._btab = self.backend.put_host(self._btab_h.copy())
            self._btab_dirty = False
        else:
            if prefix_cache:
                raise ValueError("prefix_cache=True requires kv_block_size")
            self.pool = None
            self.prefix = None
            self.cache = self.backend.put_cache(
                model,
                model.init_cache(batch_slots, max_len, kv_dtype=self.kv_dtype),
            )
        # dense cache bytes are allocation-constant; paged residency is
        # used_blocks x bytes_per_block (see kv_bytes_resident)
        self._dense_kv_bytes = (
            0 if self.paged
            else sum(int(l.nbytes) for l in jax.tree.leaves(self.cache))
        )
        self.slot_req: list[Optional[Request]] = [None] * batch_slots
        self.slot_len = np.zeros(batch_slots, np.int32)  # host mirror (counts)
        self.slot_fed = np.zeros(batch_slots, np.int32)  # prompt tokens fed
        self.waiting: deque[Request] = deque()
        self.finished: list[Request] = []
        self.rng = np.random.default_rng(seed)
        self._prefill_cache = {}
        self._prefilling: list[int] = []  # slots mid-prefill, admission order
        self._packed_shapes: set[int] = set()  # compiled T buckets
        self._admit_shapes: set[int] = set()  # compiled fused-admission buckets
        self._done_now: list[Request] = []  # requests finished in this run()
        # streaming/cancellation plumbing: pending holds dispatched-but-
        # unharvested entries, cancels is the cross-thread abort inbox
        self._pending: deque = deque()
        self._cancels: list[Request] = []
        self._cancel_lock = threading.Lock()
        # serializes state-machine drivers: run() loop iterations, step()
        # (handle-driven streaming), and inline cancellation application —
        # a cancel that read _running=False just as run() starts blocks
        # here until the in-flight iteration finishes instead of mutating
        # the slot table underneath it. Uncontended acquire per tick is
        # noise next to a ~ms dispatch.
        self._drive_lock = threading.RLock()
        self._running = False  # a run() loop (possibly another thread) drives
        # poison pill for replica-failure recovery: a cluster that declared
        # this engine dead sets it so a stuck controller thread that later
        # resumes aborts its run() at the next iteration boundary without
        # touching state the survivors have already re-homed
        self._poisoned = False
        self._stream_stats = ServeStats()  # accumulator for step()-driven serving
        # the cache is donated through all consumers — the engine never
        # holds two copies of the KV cache
        self._insert = self.backend.jit(self._insert_fn, donate_argnums=(0,))
        self._tick = self.backend.jit(
            self._tick_fn, donate_argnums=(1,),
            static_argnames=("n_steps", "smode"),
        )
        self._packed = self.backend.jit(
            self._packed_fn, donate_argnums=(1,), static_argnames=("smode",)
        )
        self._admit_prog = self.backend.jit(
            self._admit_fn, donate_argnums=(1,), static_argnames=("smode",)
        )
        if self.paged:
            # the paged twins of _tick/_packed: identical programs with the
            # block table threaded through to the (block, offset) dispatch
            self._tick_paged = self.backend.jit(
                self._tick_paged_fn, donate_argnums=(1,),
                static_argnames=("n_steps", "smode"),
            )
            self._packed_paged = self.backend.jit(
                self._packed_paged_fn, donate_argnums=(1,),
                static_argnames=("smode",),
            )
        if self.spec is not None:
            # verify programs compile at EXACT T = B*(K+1) per depth bucket
            # (K in {1,2,4,..,spec_k}): a handful of depths, so exact shapes
            # beat ladder padding — every padded row is a wasted model+
            # sampler row on the verify hot path
            self._spec_prog = self.backend.jit(
                self._spec_fn, donate_argnums=(1,),
                static_argnames=("depth_k", "smode"),
            )
            if self.paged:
                self._spec_prog_paged = self.backend.jit(
                    self._spec_paged_fn, donate_argnums=(1,),
                    static_argnames=("depth_k", "smode"),
                )
        # the legacy first-token path jits the SAME fused sampler on a
        # one-row batch: host and device sampling cannot drift apart.
        # sampf = [temperature, top_p] f32, sampi = [top_k, seed] i32 —
        # one combined upload each instead of four scalar device_puts
        self._sample1 = self.backend.jit(
            lambda row, sampf, sampi, pos, bt, bv, smode: fused_sample(
                row[None], sampf[:1], sampi[:1], sampf[1:], sampi[1:],
                pos[None], bt, bv, smode=smode,
            )[0],
            static_argnames=("smode",),
        )
        # device-resident tick state: sampled tokens, per-slot lengths
        self._last_tok = self.backend.put_state(jnp.zeros(batch_slots, jnp.int32))
        self._cur_len = self.backend.put_state(jnp.zeros(batch_slots, jnp.int32))
        # event-driven device arrays (re-uploaded only when slots change):
        # lanes rows are (ov_mask, ov_tok, ov_len, active) — one combined
        # upload instead of five tiny ones — and the per-slot sampling
        # parameter rows (temperature/top_p, top_k/seed, logit-bias pairs)
        self._lanes_idle = self.backend.put_state(
            jnp.zeros((4, batch_slots), jnp.int32)
        )
        self._put_sp(*param_rows([None] * batch_slots, np.zeros(batch_slots)))
        # cached all-zero sampler operands: every greedy (smode 0) dispatch
        # reuses these device-resident constants — the sampler arguments are
        # DEAD in the compiled greedy program, so the all-greedy hot path
        # must not pay fresh uploads for them (tiny device_puts dominate
        # small-host dispatch; C3 parity for the gated steady-state row)
        self._sp0 = (self._spf, self._spi, self._btok, self._bval)
        self._samp0f = self.backend.put_state(jnp.zeros(2, jnp.float32))
        self._samp0i = self.backend.put_state(jnp.zeros(2, jnp.int32))
        self._bias1_0t = self._btok[:1]
        self._bias1_0v = self._bval[:1]
        self._ov_mask_h = np.zeros(batch_slots, bool)  # staged override lanes
        self._ov_tok_h = np.zeros(batch_slots, np.int32)
        self._ov_len_h = np.zeros(batch_slots, np.int32)
        self._dirty = False  # overrides/active/sampling rows pending upload
        # right-padded prefill is only safe when nothing recurrent sees the
        # pad tokens: attention masks them (causal + cur_len), SSM states don't
        self._bucket_prefill = model.cfg.family in ("dense", "moe")

    # ------------------------------------------------------------ internals

    @staticmethod
    def _insert_fn(cache, one_cache, slot):
        """Scatter a B=1 prefilled cache into batched cache at ``slot``."""

        def leaf(c, o):
            return jax.lax.dynamic_update_slice_in_dim(c, o.astype(c.dtype), slot, axis=1)

        return jax.tree.map(leaf, cache, one_cache)

    def _put_sp(self, spf, spi, btok, bval) -> None:
        """Place the per-slot sampling parameter rows on device."""
        self._spf = self.backend.put_host(spf)
        self._spi = self.backend.put_host(spi)
        self._btok = self.backend.put_host(btok)
        self._bval = self.backend.put_host(bval)
        # rows stay fresh until a NEW request occupies a slot (a freed
        # slot's stale row is harmless: inactive slots' draws are masked)
        self._sp_fresh = True

    def _sp_rows(self):
        """Host-built per-slot sampling rows for the CURRENT slot pool."""
        return param_rows(
            [r.params if r is not None else None for r in self.slot_req],
            [getattr(r, "_seed", 0) if r is not None else 0 for r in self.slot_req],
        )

    def _bind(self, req: Request) -> None:
        """Resolve per-request derived sampling state once, at admission:
        the effective seed (engine-assigned when the caller left it None)
        and the precomputed stop set / sampler variant."""
        if getattr(req, "_bound", False):
            return
        p = req.params
        req._seed = p.seed if p.seed is not None else int(self.rng.integers(1 << 31))
        req._stop = frozenset(p.stop)
        req._smode = p.smode
        # per-tenant speculation toggle resolves once, at admission: an
        # opted-out tenant's slots ride the verify dispatch at depth 0
        # (exactly one sequential token per tick, nothing perturbed)
        req._spec = self.spec is not None and self.spec.enabled_for(req.tenant)
        req._bound = True

    def _tick_fn(self, params, cache, last_tok, cur_len, lanes, spf, spi,
                 btok, bval, n_steps: int = 1, smode: int = 0):
        """One fused decode-chunk dispatch: fold the admission override lanes
        into the device state, then run ``n_steps`` decode+sample steps as a
        device-side scan. Everything stays on device; the per-dispatch
        overhead (and, without donation, the KV-cache copy) amortizes over
        the whole chunk. ``lanes`` is ONE [4, B] int32 array — rows
        (ov_mask, ov_tok, ov_len, active) — because every tiny host→device
        upload costs real wall time on small hosts. Returns toks
        [n_steps, B].

        ``smode=0`` is the all-greedy fast path: plain argmax, no PRNG key
        folds and no gumbel draw (threefry is a real cost on small hosts).
        Inactive slots keep their ``last_tok`` (mid-prefill slots ride the
        batch inertly — their sampled garbage must not clobber a first
        token the packed dispatch just wrote).

        Chunking never changes results: the host only chooses ``n_steps``
        such that no slot can count-finish (and hence no admission can
        land) inside the chunk, and every sample's PRNG key is a pure
        function of (request seed, position) — identical to n_steps=1
        dispatches by construction.
        """
        ov_mask = lanes[0].astype(bool)
        active = lanes[3].astype(bool)
        last_tok = jnp.where(ov_mask, lanes[1], last_tok)
        cur_len = jnp.where(ov_mask, lanes[2], cur_len)
        adv = lanes[3]

        def step(carry, _):
            tok, cl, cache = carry
            logits, new_cache = self.model.decode_step(
                params, cache, {"tokens": tok[:, None]}, cl
            )
            if self.model.cfg.family == "ssm":
                # recurrent state has no position axis: an inactive (mid-
                # prefill or empty) slot must not fold the batch's rider
                # token into its state — mask its update. Attention slots
                # instead rely on the next pack overwriting the garbage
                # row at cur_len.
                new_cache = jax.tree.map(
                    lambda n, c: jnp.where(
                        active.reshape((1, -1) + (1,) * (n.ndim - 2)), n, c
                    ),
                    new_cache, cache,
                )
            cache = new_cache
            new = fused_sample(
                logits[:, 0], spf[0], spi[0], spf[1], spi[1], cl,
                btok, bval, smode=smode,
            )
            tok = jnp.where(active, new, tok)
            return (tok, cl + adv, cache), tok

        (last_tok, cur_len, cache), toks = jax.lax.scan(
            step, (last_tok, cur_len, cache), None, length=n_steps
        )
        return toks, last_tok, cur_len, cache

    def _tick_paged_fn(self, params, cache, btab, last_tok, cur_len, lanes,
                       spf, spi, btok, bval, n_steps: int = 1, smode: int = 0):
        """The decode-chunk program over the block-paged pool: identical to
        :meth:`_tick_fn` except the model step resolves every (slot,
        cur_len) through ``btab`` — the per-request reconfiguration is a
        host-written table consulted by the index maps, never a hot-loop
        cost (a chunk with an unchanged slot set re-uses the resident
        table and ships ZERO host arrays, exactly like the dense path)."""
        ov_mask = lanes[0].astype(bool)
        active = lanes[3].astype(bool)
        last_tok = jnp.where(ov_mask, lanes[1], last_tok)
        cur_len = jnp.where(ov_mask, lanes[2], cur_len)
        adv = lanes[3]

        def step(carry, _):
            tok, cl, cache = carry
            logits, cache = self.model.decode_step(
                params, cache, {"tokens": tok[:, None]}, cl, block_tables=btab
            )
            new = fused_sample(
                logits[:, 0], spf[0], spi[0], spf[1], spi[1], cl,
                btok, bval, smode=smode,
            )
            tok = jnp.where(active, new, tok)
            return (tok, cl + adv, cache), tok

        (last_tok, cur_len, cache), toks = jax.lax.scan(
            step, (last_tok, cur_len, cache), None, length=n_steps
        )
        return toks, last_tok, cur_len, cache

    def _packed_paged_fn(self, params, cache, btab, last_tok, desc, meta,
                         spf, spi, btok, bval, smode: int = 0):
        """The ragged-pack program over the block-paged pool: identical to
        :meth:`_packed_fn` with the block table threaded to the paged
        scatter/attention. Same descriptors, same meta layout, same
        sampling — which is why paged greedy streams are bit-identical to
        the dense engine's."""
        b = self.B
        new_len = meta[:b]
        sample_idx = meta[b : 2 * b]
        sample_mask = meta[2 * b : 3 * b].astype(bool)
        pack_slots = meta[3 * b :]
        logits, cache = self.model.packed_step(
            params, cache, desc[0], desc[1], desc[2],
            out_rows=sample_idx, pack_slots=pack_slots, block_tables=btab,
        )
        sampled = fused_sample(
            logits, spf[0], spi[0], spf[1], spi[1], new_len - 1,
            btok, bval, smode=smode,
        )
        last_tok = jnp.where(sample_mask, sampled, last_tok)
        return sampled, last_tok, new_len, cache

    def _packed_fn(self, params, cache, last_tok, desc, meta, spf, spi,
                   btok, bval, smode: int = 0):
        """One ragged prefill dispatch: a flat [T_bucket] pack of prompt
        chunk tokens from every admitting slot runs through the packed
        model step; a slot whose prompt COMPLETES in this pack samples its
        first token from its final prompt position, device-side, alongside
        everyone else's work — the legacy engine's blocking logits transfer
        + host sample per admission disappears.

        The host-built arrays arrive as combined int32 uploads (tiny
        device_puts dominate small-host dispatch): ``desc`` [3, T_bucket]
        rows (chunk token, local slot, position), ``meta`` [3B + pack
        width] = new_len | sample_idx | sample_mask | pack_slots, where
        new_len is the host-computed per-slot cache count after this pack
        (the host knows every count in advance); the sampled first token's
        PRNG position is its final prompt index, ``new_len - 1``. Returns
        (sampled [B], last_tok, cur_len, cache)."""
        b = self.B
        new_len = meta[:b]
        sample_idx = meta[b : 2 * b]
        sample_mask = meta[2 * b : 3 * b].astype(bool)
        pack_slots = meta[3 * b :]
        logits, cache = self.model.packed_step(
            params, cache, desc[0], desc[1], desc[2],
            out_rows=sample_idx, pack_slots=pack_slots, max_len=self.max_len,
        )
        sampled = fused_sample(
            logits, spf[0], spi[0], spf[1], spi[1], new_len - 1,
            btok, bval, smode=smode,
        )
        last_tok = jnp.where(sample_mask, sampled, last_tok)
        return sampled, last_tok, new_len, cache

    def _spec_fn(self, params, cache, last_tok, cur_len, pack, spf,
                 spi, btok, bval, depth_k: int = 1, smode: int = 0):
        """One draft-and-verify dispatch: the packed ragged model step over
        slot-major verify rows ``[last_token, draft_1 .. draft_K]`` per
        slot (T = B*(K+1), exact — no bucket padding), then the seeded
        exact-match acceptance (:func:`spec_verify`) device-side.  The
        verify pack reuses the SAME descriptors, scatter and ragged
        attention as the prefill pack — row (i, j) scatters at (slot i,
        pos cl+j) and attends kpos <= tok_pos, so each row sees exactly
        the context plus the drafts before it, and the packed logits are
        bitwise equal to j sequential decode steps.

        ``pack`` is ONE [3, T + B] i32 upload — the first T columns the
        usual (token, slot, position) descriptor triples, the trailing B
        columns the per-slot meta rows (depth, active, cl); fusing them
        halves the fixed per-upload dispatch cost, which profiles as a
        measurable slice of the host-blocking verify tick.  Descriptor
        rows past a slot's depth carry the out-of-range position sentinel
        (scatter dropped) and a depth-masked acceptance.  Rejected rows
        need no rollback: their K/V sits at positions >= the committed
        ``cur_len``, invisible to every masked read and overwritten by the
        next dispatch's scatters — the argument slot reuse already relies
        on.  Inactive slots (mid-prefill neighbours) pass through
        untouched.  Returns (targets [B, K+1], commit [B], last_tok,
        cur_len, cache)."""
        b, w = self.B, depth_k + 1
        desc, meta = pack[:, : b * w], pack[:, b * w :]
        depth, act, cl = meta[0], meta[1], meta[2]
        active = act.astype(bool)
        logits, cache = self.model.packed_step(
            params, cache, desc[0], desc[1], desc[2]
        )
        drafts = desc[0][: b * w].reshape(b, w)[:, 1:]
        targets, n_acc, commit = spec_verify(
            logits[: b * w], drafts, depth, act, spf[0], spi[0], spf[1],
            spi[1], cl, btok, bval, smode=smode,
        )
        last_tok = jnp.where(active, targets[jnp.arange(b), n_acc], last_tok)
        cur_len = jnp.where(active, cl + commit, cur_len)
        return targets, commit, last_tok, cur_len, cache

    def _spec_paged_fn(self, params, cache, btab, last_tok, cur_len, pack,
                       spf, spi, btok, bval, depth_k: int = 1,
                       smode: int = 0):
        """The verify program over the block-paged pool: identical to
        :meth:`_spec_fn` with the block table threaded through.  Paged
        speculation releases NOTHING on rejection — admission reserved the
        slot's whole worst-case table, the verify rows only write
        positions inside it (and past any shared prefix, so COW blocks are
        never touched)."""
        b, w = self.B, depth_k + 1
        desc, meta = pack[:, : b * w], pack[:, b * w :]
        depth, act, cl = meta[0], meta[1], meta[2]
        active = act.astype(bool)
        logits, cache = self.model.packed_step(
            params, cache, desc[0], desc[1], desc[2], block_tables=btab
        )
        drafts = desc[0][: b * w].reshape(b, w)[:, 1:]
        targets, n_acc, commit = spec_verify(
            logits[: b * w], drafts, depth, act, spf[0], spi[0], spf[1],
            spi[1], cl, btok, bval, smode=smode,
        )
        last_tok = jnp.where(active, targets[jnp.arange(b), n_acc], last_tok)
        cur_len = jnp.where(active, cl + commit, cur_len)
        return targets, commit, last_tok, cur_len, cache

    def _admit_fn(self, params, cache, toks, slot, last_pos, last_tok,
                  cur_len, sampf, sampi, btok, bval, smode: int = 0):
        """One fused async admission (unified mode, prompt ≤ budget): dense
        prefill + cache insert + the first token sampled on device from the
        last REAL prompt position + tick-state update, all in ONE dispatch
        that nothing waits on. The legacy path's blocking logits transfer
        + host-side sample per admission — the pipeline bubble that stalls
        every decode slot — does not exist here; the newly admitted slot
        starts decoding in the same loop iteration."""
        logits, one_cache = self.model.prefill(
            params, {"tokens": toks}, self.max_len
        )
        cache = self._insert_fn(cache, one_cache, slot)
        row = logits[0, last_pos]  # [V]
        tok = fused_sample(
            row[None], sampf[:1], sampi[:1], sampf[1:], sampi[1:],
            last_pos[None], btok, bval, smode=smode,
        )[0]
        last_tok = last_tok.at[slot].set(tok)
        cur_len = cur_len.at[slot].set(last_pos + 1)
        return tok, last_tok, cur_len, cache

    def _prefill_one(self, req: Request, slot: int, stats: Optional[ServeStats]):
        s = len(req.prompt)
        sb = _bucket_len(s, self.max_len) if self._bucket_prefill else s
        sb = max(sb, s)
        if sb not in self._prefill_cache:
            self._prefill_cache[sb] = self.backend.jit(
                lambda p, b: self.model.prefill(p, b, self.max_len)
            )
            if stats is not None:
                stats.prefill_compiles += 1
        toks = np.zeros((1, sb), np.int32)
        toks[0, :s] = req.prompt
        logits, one_cache = self._prefill_cache[sb](
            self.params, {"tokens": self.backend.put_host(toks)}
        )
        self.cache = self._insert(self.cache, one_cache, jnp.int32(slot))
        return logits[0, s - 1]  # last REAL position's logits (device row)

    def _admit_samp(self, req: Request):
        """Per-request admission sampler operands ``(sampf, sampi, btok,
        bval)``. A greedy request reuses the cached device-resident zeros —
        its compiled program never reads them, so the all-greedy admission
        path uploads NOTHING beyond what the pre-SamplingParams engine did."""
        if req._smode == SMODE_GREEDY:
            return self._samp0f, self._samp0i, self._bias1_0t, self._bias1_0v
        p = req.params
        bt, bv = bias_row(p)
        return (
            self.backend.put_host(np.asarray([p.temperature, p.top_p], np.float32)),
            self.backend.put_host(np.asarray([p.top_k, req._seed], np.int32)),
            self.backend.put_host(bt[None]),
            self.backend.put_host(bv[None]),
        )

    def _sample_first(self, row, req: Request) -> int:
        """Legacy-path first-token sample: the SAME fused sampler as every
        device dispatch, jitted on a one-row batch (blocking — the legacy
        admission is synchronous by definition). The row is cast to f32
        BEFORE the jit boundary so the program prewarm() compiled (an f32
        dummy row) serves every model dtype — a bf16 arch must not pay a
        sampler compile at its first sampled admission."""
        sampf, sampi, bt, bv = self._admit_samp(req)
        return int(
            self._sample1(
                row.astype(jnp.float32), sampf, sampi,
                jnp.int32(len(req.prompt) - 1), bt, bv, smode=req._smode,
            )
        )

    # --------------------------------------------------------- token harvest

    def _credit(self, req: Request, tok: int, now: float,
                stats: Optional[ServeStats], first: bool = False) -> None:
        """Append one harvested token value to its request, detecting stop
        tokens at the host crossing that already exists. The stop token is
        itself emitted and counted into ``n_generated`` — exactly like the
        final token of a ``max_new`` window — and any in-flight values past
        it (or past a cancellation) are discarded here, so ``generated``
        is always the final visible prefix (a streaming iterator never
        sees a token that later disappears). A discarded decode value is
        also refunded from ``stats.total_tokens`` (it was counted at
        dispatch), so reported throughput only counts emitted tokens."""
        if req.finish_reason in ("stop", "cancelled") or (
            len(req.generated) >= req.n_generated
        ):
            # overrun values past a stop/cancel; first tokens (admit/packed
            # entries) were never in total_tokens, decode values were
            if stats is not None and not first:
                stats.total_tokens -= 1
            return
        req.generated.append(tok)
        if first and req.first_token_at is None:
            req.first_token_at = now
        if tok in req._stop:
            # stop wins over a simultaneous max_new boundary: the request
            # ended at this token either way, and the reason says why
            req.finish_reason = "stop"
            req.n_generated = len(req.generated)
            req.done_at = now

    @staticmethod
    def _stamp(req: Request, now: float) -> None:
        # done_at was stamped at dispatch-enqueue (counts-only
        # bookkeeping); pull it forward to when the values actually
        # reached the host so TPOT never goes negative and the final
        # chunk's device compute is not silently excluded
        if req.done_at is not None:
            req.done_at = max(req.done_at, now)

    def _harvest(self, entry) -> None:
        """Blockingly pull one dispatch's sampled tokens and credit the
        slots' requests. Called one dispatch behind, so this host transfer
        overlaps the next dispatch's device compute. Packed entries also
        stamp first-token availability (TTFT) — the value provably exists
        on the host at harvest time. The entry carries the stats object
        that counted its dispatch, so a discard refund always lands on the
        counter that was incremented — even when a chunk dispatched under
        step()-driven streaming is harvested inside a later run()."""
        kind, tok_dev, items, stats = entry
        toks = np.asarray(tok_dev)
        now = time.perf_counter()

        if kind == "admit":  # fused admission: one scalar first token
            slot, req = items
            self._credit(req, int(toks), now, stats, first=True)
            self._stamp(req, now)
        elif kind == "packed":  # [B] one sample per flagged slot
            for slot, req, is_first in items:
                self._credit(req, int(toks[slot]), now, stats, first=is_first)
                self._stamp(req, now)
        else:  # decode chunk: [n_steps, B]
            for slot, req in items:
                if not req._stop and len(req.generated) + len(toks) <= req.n_generated:
                    # no stop set and no overrun: bulk-extend (the all-greedy
                    # steady state takes this path for every chunk)
                    req.generated.extend(int(t) for t in toks[:, slot])
                else:
                    for t in toks[:, slot]:
                        self._credit(req, int(t), now, stats)
                self._stamp(req, now)

    def _drain_pending(self) -> None:
        while self._pending:
            self._harvest(self._pending.popleft())

    def _flush_events(self):
        """Upload pending slot changes; returns this tick's [4, B] lanes."""
        if not self._dirty:
            return self._lanes_idle
        lanes = np.zeros((4, self.B), np.int32)
        # one-shot override rows: fresh numpy every flush — CPU device_put
        # of a numpy array can be zero-copy/deferred, so handing jax a live
        # staging buffer the host later mutates races the in-flight
        # dispatch (observed as override lanes reading zeros mid-run)
        lanes[0] = self._ov_mask_h
        lanes[1] = self._ov_tok_h
        lanes[2] = self._ov_len_h
        # active == DECODING: a mid-prefill slot rides decode chunks inertly
        # (no cur_len advance, last_tok preserved) until its pack completes
        lanes[3] = [
            r is not None and self.slot_fed[i] >= len(r.prompt)
            for i, r in enumerate(self.slot_req)
        ]
        # the per-slot sampling rows are DEAD in every smode-0 program: an
        # all-greedy slot pool skips the rebuild entirely, and a flush
        # whose only change is a freed slot (rows still fresh) skips it too
        # — _packed_tick may also have rebuilt them earlier this iteration
        if not self._sp_fresh and any(
            r is not None and r._smode for r in self.slot_req
        ):
            self._put_sp(*self._sp_rows())
        # the overrides apply exactly once; later idle ticks reuse a cached
        # ov-zeroed copy with the same active row
        idle = lanes.copy()
        idle[:3] = 0
        self._lanes_idle = self.backend.put_host(idle)
        self._ov_mask_h[:] = False
        self._dirty = False
        return self.backend.put_host(lanes)

    def _flush_btab(self):
        """Upload the block table if any slot's mapping changed; returns
        the device-resident [B, max_blocks] table. Steady-state chunks with
        an unchanged slot set reuse the resident copy (no upload). The
        fresh ``copy()`` matters: releasing a slot NB's its host row, and
        the NEXT dispatch must see that before the freed blocks can be
        re-scattered by a new owner — handing jax a live staging buffer the
        host later mutates races the in-flight dispatch."""
        if self._btab_dirty:
            self._btab = self.backend.put_host(self._btab_h.copy())
            self._btab_dirty = False
        return self._btab

    def _release_slot_blocks(self, slot: int) -> None:
        """Drop the slot's references on its blocks (finish/cancel). Blocks
        the prefix tree retains keep their references and stay resident; the
        rest return to the free list and are re-admittable immediately —
        any in-flight dispatch that still reads them was enqueued before
        the next owner's scatter, so device ordering keeps it correct
        (the same argument as dense slot reuse)."""
        self.pool.release_all(self._slot_blocks[slot])
        self._slot_blocks[slot] = []
        self._btab_h[slot, :] = self.num_blocks
        self._btab_dirty = True

    # ------------------------------------------------------------------ API

    def prewarm(self, sampling: bool = False) -> None:
        """Compile every dispatch variant this engine can hit, before any
        request arrives (production serving compiles once, then serves):
        the decode-chunk scan depths up to ``max_chunk`` and — in unified
        mode — every packed T bucket up to ``prefill_budget`` plus the
        fused-admission prompt buckets. A compile landing inside a live
        arrival stream stalls every queued request's TTFT; this moves all
        of them off the serving path. ``sampling=True`` additionally
        compiles every sampler variant (gumbel temperature + masked
        top-k/top-p) of each dispatch — greedy-only deployments skip them,
        a mixed-sampling deployment should not let its first temperature
        or nucleus request pay the compile. Call on an IDLE engine (before
        serving): the dummy fused-admission dispatches overwrite slot 0's
        cache row."""
        smodes = (0, 1, 2) if sampling else (0,)
        k = 1
        while k <= self.max_chunk:
            for sm in smodes:
                if self.paged:
                    # all-sentinel block table: every scatter drops, every
                    # gather clamps — the pool is untouched by the warmup
                    toks, _lt, _cl, self.cache = self._tick_paged(
                        self.params, self.cache, self._btab,
                        self._last_tok, self._cur_len,
                        self._lanes_idle, self._spf, self._spi, self._btok,
                        self._bval, n_steps=k, smode=sm,
                    )
                else:
                    toks, _lt, _cl, self.cache = self._tick(
                        self.params, self.cache, self._last_tok, self._cur_len,
                        self._lanes_idle, self._spf, self._spi, self._btok,
                        self._bval, n_steps=k, smode=sm,
                    )
                jax.block_until_ready(toks)
            k *= 2
        if not self.unified:
            if sampling:  # the legacy first-token path's sampler variants
                row = self.backend.put_host(np.zeros(self.model.cfg.vocab_size, np.float32))
                for sm in smodes:
                    jax.block_until_ready(self._sample1(
                        row, self._samp0f, self._samp0i, jnp.int32(0),
                        self._bias1_0t, self._bias1_0v, smode=sm,
                    ))
            return
        # the EXACT T-bucket ladder _bucket_tokens can produce, including
        # the doubling tail beyond _T_BUCKETS for very large budgets
        top = _bucket_tokens(self.prefill_budget)
        tb_ladder = [b for b in _T_BUCKETS if b <= top]
        b = _T_BUCKETS[-1]
        while b < top:
            b *= 2
            tb_ladder.append(b)
        for tb in tb_ladder:
            if tb in self._packed_shapes:
                continue
            desc, meta = self._padding_pack(tb)
            for sm in smodes:
                if self.paged:
                    toks, _lt, _cl, self.cache = self._packed_paged(
                        self.params, self.cache, self._btab, self._last_tok,
                        self.backend.put_host(desc), self.backend.put_host(meta),
                        self._spf, self._spi, self._btok, self._bval, smode=sm,
                    )
                else:
                    toks, _lt, _cl, self.cache = self._packed(
                        self.params, self.cache, self._last_tok,
                        self.backend.put_host(desc), self.backend.put_host(meta),
                        self._spf, self._spi, self._btok, self._bval, smode=sm,
                    )
                jax.block_until_ready(toks)
            self._packed_shapes.add(tb)
        if self.spec is not None:
            # the verify depth ladder {1, 2, 4, .., spec_k} — the only
            # widths _spec_tick can dispatch — plus the drafter's own
            # programs.  All-padding packs (pos = max_len, every slot
            # inactive) so the warmup commits nothing and touches no slot.
            self.drafter.prewarm()
            kk = 1
            while True:
                pack = np.zeros((3, self.B * (kk + 1) + self.B), np.int32)
                pack[2, : self.B * (kk + 1)] = self.max_len
                for sm in smodes:
                    if self.paged:
                        tg, _c, _lt, _cl, self.cache = self._spec_prog_paged(
                            self.params, self.cache, self._btab,
                            self._last_tok, self._cur_len,
                            self.backend.put_host(pack),
                            self._spf, self._spi, self._btok, self._bval,
                            depth_k=kk, smode=sm,
                        )
                    else:
                        tg, _c, _lt, _cl, self.cache = self._spec_prog(
                            self.params, self.cache, self._last_tok,
                            self._cur_len, self.backend.put_host(pack),
                            self._spf, self._spi, self._btok, self._bval,
                            depth_k=kk, smode=sm,
                        )
                    jax.block_until_ready(tg)
                    self._spec_shapes.add((kk, sm))
                if kk >= self.spec_k:
                    break
                kk *= 2
        if self.paged or self._chunk_only_admit:
            # paged, quantized-KV and SSM admission route every request
            # through the packed tier (one code path writes the cache /
            # pool / state) — no fused-admission shapes exist to warm
            return
        # the EXACT prompt buckets _admit_unified can produce: every power
        # of two up to the fused-tier limit, plus the max_len-capped bucket
        # a non-pow2 max_len introduces
        top_prompt = min(self.prefill_budget, self.max_len - 1)
        sizes = [top_prompt]
        b = 1
        while b <= top_prompt:
            sizes.append(b)
            b *= 2
        for sb in sorted({_bucket_len(s, self.max_len) for s in sizes}):
            if sb in self._admit_shapes:
                continue
            for sm in smodes:
                tok, _lt, _cl, self.cache = self._admit_prog(
                    self.params, self.cache,
                    self.backend.put_host(np.zeros((1, sb), np.int32)),
                    jnp.int32(0), jnp.int32(sb - 1), self._last_tok,
                    self._cur_len, self._samp0f, self._samp0i,
                    self._bias1_0t, self._bias1_0v, smode=sm,
                )
                jax.block_until_ready(tok)
            self._admit_shapes.add(sb)

    def _padding_pack(self, tb: int) -> tuple[np.ndarray, np.ndarray]:
        """(desc, meta) of an all-padding pack of ``tb`` tokens: scatters
        dropped (pos = max_len), no slot sampled, cur_len passed through
        unchanged."""
        desc = np.zeros((3, tb), np.int32)
        desc[2] = self.max_len
        meta = np.concatenate(
            [
                self.slot_len,
                np.zeros(2 * self.B, np.int32),
                np.zeros(self._pack_width, np.int32),
            ]
        )
        return desc, meta

    def lower_dispatch(self) -> dict:
        """The two hot dispatch programs — one greedy decode tick and the
        widest packed T bucket (unified engines) — lowered against the live
        operands, nothing executed: ``{"tick": Lowered, "packed":
        Lowered}``. A caller reads what they compile to, e.g. that the
        attention kernels are in them (``tpu_custom_call``) and no plain-XLA
        fallback replaced them."""
        sp = (self._spf, self._spi, self._btok, self._bval)
        if self.paged:
            out = {"tick": self._tick_paged.lower(
                self.params, self.cache, self._btab, self._last_tok,
                self._cur_len, self._lanes_idle, *sp, n_steps=1, smode=0,
            )}
        else:
            out = {"tick": self._tick.lower(
                self.params, self.cache, self._last_tok, self._cur_len,
                self._lanes_idle, *sp, n_steps=1, smode=0,
            )}
        if self.unified:
            desc, meta = (
                self.backend.put_host(a)
                for a in self._padding_pack(_bucket_tokens(self.prefill_budget))
            )
            out["packed"] = (
                self._packed_paged.lower(
                    self.params, self.cache, self._btab, self._last_tok,
                    desc, meta, *sp, smode=0,
                )
                if self.paged
                else self._packed.lower(
                    self.params, self.cache, self._last_tok, desc, meta, *sp,
                    smode=0,
                )
            )
        return out

    def reset(self) -> None:
        """Return an IDLE engine to its just-constructed serving state.

        Device tick state, override staging and slot bookkeeping are
        re-zeroed; compiled programs and the (garbage-tolerant) KV cache
        survive, so re-entering a previously-built cluster mode costs no
        recompiles and no cache realloc — the warm half of the paper's
        cheap CSR-write reconfiguration. Refuses to reset mid-flight."""
        assert all(r is None for r in self.slot_req), "reset() on a busy engine"
        self.slot_len[:] = 0
        self.slot_fed[:] = 0
        self.waiting.clear()
        self.finished = []
        self._prefilling.clear()
        self._done_now = []
        self._pending.clear()
        self._cancels.clear()
        self._stream_stats = ServeStats()
        self.rng = np.random.default_rng(self.seed)
        self._last_tok = self.backend.put_state(jnp.zeros(self.B, jnp.int32))
        self._cur_len = self.backend.put_state(jnp.zeros(self.B, jnp.int32))
        self._lanes_idle = self.backend.put_state(jnp.zeros((4, self.B), jnp.int32))
        self._put_sp(*param_rows([None] * self.B, np.zeros(self.B)))
        self._ov_mask_h[:] = False
        self._ov_tok_h[:] = 0
        self._ov_len_h[:] = 0
        self._dirty = False
        if self.spec is not None:
            self._spec_ewma[:] = 1.0
            for i in range(self.B):
                self.drafter.reset_slot(i)
        if self.paged:
            if self.prefix is not None:
                self.prefix.clear()
            self.pool.reset()
            self._slot_blocks = [[] for _ in range(self.B)]
            self._btab_h[:] = self.num_blocks
            self._btab = self.backend.put_host(self._btab_h.copy())
            self._btab_dirty = False

    def submit(self, req: Request) -> RequestHandle:
        assert len(req.prompt) < self.max_len, (len(req.prompt), self.max_len)
        if self.paged:
            need = blocks_for(
                len(req.prompt), req.params.max_new, self.max_len,
                self.kv_block_size,
            )
            if need > self.num_blocks:
                # an admission-time wait could never resolve — reject at
                # the submission boundary instead of spinning forever
                raise AdmissionRejected(
                    "infeasible",
                    f"request needs {need} KV blocks, pool holds "
                    f"{self.num_blocks}",
                )
        req.submitted_at = time.perf_counter()
        self.waiting.append(req)
        return RequestHandle(req, self)

    # ------------------------------------------------------------- lifecycle

    def cancel(self, req: Request) -> None:
        """Abort a request (thread-safe): enqueue the cancellation and — if
        no run loop owns the engine — apply it immediately. A controller
        thread mid-``run()`` applies queued cancels at its next scheduling
        iteration; the freed slot is re-admittable the same iteration, and
        no other slot's stream is perturbed (per-request sampling keys)."""
        with self._cancel_lock:
            # append AND the _running read happen under the lock: an
            # unlocked append could land on a list _apply_cancels already
            # swapped out (silently losing the cancel), and run() flips
            # _running under the same lock so inline application can't
            # overlap a starting serving loop
            self._cancels.append(req)
            running = self._running
        if not running:
            with self._drive_lock:
                self._apply_cancels(self._stream_stats)

    def _apply_cancels(self, stats: ServeStats) -> None:
        if not self._cancels:  # steady state: no lock, no list churn
            return
        with self._cancel_lock:
            cancels, self._cancels = self._cancels, []
            for req in cancels:
                if req.finish_reason is not None:
                    continue  # finished (or already cancelled) — nothing to free
                if req in self.waiting:
                    self.waiting.remove(req)
                for slot, r in enumerate(self.slot_req):
                    if r is req:  # free the slot mid-stream
                        self.slot_req[slot] = None
                        self.slot_len[slot] = 0
                        self.slot_fed[slot] = 0
                        if slot in self._prefilling:
                            self._prefilling.remove(slot)
                        if self.paged:  # cancel frees the blocks mid-stream
                            self._release_slot_blocks(slot)
                        self._ov_mask_h[slot] = False  # unflushed admission override
                        self._dirty = True
                req.finish_reason = "cancelled"
                req.n_generated = len(req.generated)  # in-flight values discarded
                req.done_at = time.perf_counter()
                self.finished.append(req)
                self._done_now.append(req)
                stats.cancelled += 1

    def _release_stopped(self, stats: ServeStats) -> None:
        """Free the slot of any request whose harvest found a stop token
        (value-dependent termination is detected one dispatch behind; the
        slot's overrun chunk, if any, was discarded at credit time)."""
        for slot, r in enumerate(self.slot_req):
            if r is not None and r.finish_reason == "stop":
                self._finish(r, slot, stats)

    def _finish(self, req: Request, slot: int, stats: Optional[ServeStats],
                reason: str = "length") -> None:
        if req.finish_reason is None:
            req.finish_reason = reason
        if req.done_at is None:
            req.done_at = time.perf_counter()
        self.finished.append(req)
        self._done_now.append(req)
        self.slot_req[slot] = None
        self.slot_len[slot] = 0
        if self.paged:
            self._release_slot_blocks(slot)
        if stats is not None:
            stats.total_requests += 1
        self._dirty = True

    def _admit(self, stats: Optional[ServeStats] = None) -> None:
        """Legacy admission: synchronous B=1 prefill + cache insert. The
        first token runs through the SAME fused sampler as the device
        dispatches (one-row jit), so legacy and unified streams stay
        bit-identical under every SamplingParams."""
        for slot in range(self.B):
            while self.slot_req[slot] is None and self.waiting:
                req = self.waiting.popleft()
                self._bind(req)
                row = self._prefill_one(req, slot, stats)
                tok = self._sample_first(row, req)
                now = time.perf_counter()
                req.generated.append(tok)
                req.n_generated = 1
                req.first_token_at = now
                if tok in req._stop or req.n_generated >= req.params.max_new:
                    # nothing left to decode (stop token, or max_new=1):
                    # finish without ever occupying the slot. A first-token
                    # stop counts into n_generated exactly like a
                    # max_new=1 boundary — one emitted token either way.
                    req.finish_reason = "stop" if tok in req._stop else "length"
                    req.done_at = now
                    self.finished.append(req)
                    self._done_now.append(req)
                    if stats is not None:
                        stats.total_requests += 1
                    continue
                self.slot_req[slot] = req
                self._sp_fresh = False  # a new occupant's row must upload
                self.slot_len[slot] = len(req.prompt)
                self.slot_fed[slot] = len(req.prompt)
                self._ov_mask_h[slot] = True
                self._ov_tok_h[slot] = tok
                self._ov_len_h[slot] = len(req.prompt)
                self._dirty = True

    def _admit_unified(self, stats, pending: deque) -> None:
        """Unified admission — two tiers, neither of which ever blocks the
        host or stalls a decode slot:

        * prompt ≤ ``prefill_budget``: ONE fused async dispatch (dense
          prefill + insert + device-side first-token sample); the slot is
          decoding by the next dispatch in the same loop iteration.
        * longer prompts: bound to the slot and fed as ragged packed
          chunks of ≤ budget tokens per tick (Sarathi-style), so a long
          admission costs each tick only one bounded pack.
        """
        for slot in range(self.B):
            while self.slot_req[slot] is None and self.waiting:
                req = self.waiting.popleft()
                self._bind(req)
                s = len(req.prompt)
                self.slot_req[slot] = req
                self._sp_fresh = False  # a new occupant's row must upload
                self._dirty = True
                if self.spec is not None:
                    self._spec_ewma[slot] = 1.0  # optimistic: probe deep first
                    self.drafter.reset_slot(slot)
                if self._chunk_only_admit or s > self.prefill_budget:
                    # chunked ragged tier. Quantized-KV and SSM engines
                    # route EVERY admission here: the fused tier's
                    # model.prefill builds a scale-less B=1 cache that
                    # cannot insert into a scale-bearing one (quant), and
                    # an exact-length prefill would compile per prompt
                    # length (ssm — the chunk scan reuses the T buckets).
                    self.slot_len[slot] = 0
                    self.slot_fed[slot] = 0
                    self._prefilling.append(slot)
                    continue
                sb = _bucket_len(s, self.max_len) if self._bucket_prefill else s
                if sb not in self._admit_shapes:
                    self._admit_shapes.add(sb)
                    if stats is not None:
                        stats.prefill_compiles += 1
                toks = np.zeros((1, sb), np.int32)
                toks[0, :s] = req.prompt
                sampf, sampi, bt, bv = self._admit_samp(req)
                tok, self._last_tok, self._cur_len, self.cache = (
                    self._admit_prog(
                        self.params, self.cache, self.backend.put_host(toks),
                        jnp.int32(slot), jnp.int32(s - 1), self._last_tok,
                        self._cur_len, sampf, sampi, bt, bv,
                        smode=req._smode,
                    )
                )
                self.slot_len[slot] = s
                self.slot_fed[slot] = s
                req.n_generated += 1  # first token (in flight; counts-only
                pending.append(("admit", tok, (slot, req), stats))
                if req.n_generated >= req.params.max_new:  # bookkeeping)
                    self._finish(req, slot, stats)

    def _admit_paged(self, stats) -> None:
        """Paged admission: consult the prefix tree, reserve the request's
        ENTIRE worst-case block table, and bind the slot to the chunked
        ragged tier starting at the first unmatched position.

        * The radix tree (when enabled) yields the longest block-aligned
          shared prefix; those blocks enter the table read-only and
          ``slot_fed`` starts past them — matched tokens are never re-fed,
          so a repeated system prompt's prefill collapses to its tail
          (admission TTFT ∝ unmatched tokens).
        * Allocation is all-or-nothing and up front (``blocks_for``):
          decode can never run out of blocks mid-stream, and pool pressure
          surfaces exactly here — the request stays at the head of the
          queue and WAITS (after trying LRU eviction of tree-only blocks)
          until a finishing request frees capacity. Nothing crashes, no
          other slot is perturbed.
        * Every admission — even a one-token prompt — runs the packed
          tier: one code path writes the pool, so the COW invariant
          (shared blocks are never scattered into) has a single proof
          point.
        """
        for slot in range(self.B):
            while self.slot_req[slot] is None and self.waiting:
                req = self.waiting[0]
                self._bind(req)
                s = len(req.prompt)
                need_total = blocks_for(
                    s, req.params.max_new, self.max_len, self.kv_block_size
                )
                shared: list[int] = []
                matched = 0
                if self.prefix is not None:
                    shared, matched = self.prefix.match(req.prompt)
                need = need_total - len(shared)
                if not self.pool.can_alloc(need):
                    if self.prefix is not None:
                        self.prefix.evict(need - self.pool.free)
                    if not self.pool.can_alloc(need):
                        # pool exhausted: release the matched references
                        # and leave the request waiting, FCFS order intact
                        self.pool.alloc_failures += 1
                        self.pool.release_all(shared)
                        return
                self.waiting.popleft()
                blocks = shared + self.pool.alloc(need)
                self._slot_blocks[slot] = blocks
                row = self._btab_h[slot]
                row[:] = self.num_blocks
                row[: len(blocks)] = blocks
                self._btab_dirty = True
                self.slot_req[slot] = req
                self._sp_fresh = False  # a new occupant's row must upload
                self._dirty = True
                if self.spec is not None:
                    self._spec_ewma[slot] = 1.0  # optimistic: probe deep first
                    self.drafter.reset_slot(slot)
                self.slot_len[slot] = matched
                self.slot_fed[slot] = matched
                self._prefilling.append(slot)

    # ------------------------------------------------------------ tick paths

    def _packed_tick(self, stats: ServeStats, pending: deque) -> None:
        """Build and dispatch one ragged prefill pack: up to
        ``prefill_budget`` prompt tokens (FCFS across the admitting slots),
        padded to a T bucket. Decode slots are untouched here — the run
        loop rides a fused decode chunk alongside every pack, so admission
        work and decode progress share each loop iteration instead of
        queueing behind each other."""
        entries: list[tuple[int, int, int]] = []  # (token, LOCAL slot, pos)
        sample_idx = np.zeros(self.B, np.int32)
        sample_mask = np.zeros(self.B, bool)
        # the pack spans at most _pack_width admitting slots: attention
        # work (and the compile count — one variant) scales with the pack,
        # not the slot pool; later admissions simply join the next tick's
        # pack. SSM packs are width 1 (one contiguous stream per chunk).
        pack_slots = np.zeros(self._pack_width, np.int32)
        budget = self.prefill_budget
        completed: list[int] = []
        for local, i in enumerate(self._prefilling[: self._pack_width]):
            if budget <= 0:
                break
            pack_slots[local] = i
            req = self.slot_req[i]
            fed = int(self.slot_fed[i])
            n = min(budget, len(req.prompt) - fed)
            budget -= n
            for j in range(n):
                entries.append((int(req.prompt[fed + j]), local, fed + j))
            self.slot_fed[i] = fed + n
            self.slot_len[i] = fed + n
            if fed + n == len(req.prompt):
                sample_idx[i] = len(entries) - 1  # the final prompt token
                sample_mask[i] = True
                completed.append(i)
                self._prefilling.remove(i)
                self._dirty = True  # becomes an active decoder
        tb = _bucket_tokens(len(entries))
        if tb not in self._packed_shapes:
            self._packed_shapes.add(tb)
            stats.prefill_compiles += 1
        # combined uploads, built fresh every tick (CPU device_put can
        # be zero-copy, so jax must never see a buffer the host mutates
        # later). Padding tokens scatter out of bounds (dropped) and attend
        # slot 0 with an all-valid mask; their output rows are never sampled
        desc = np.zeros((3, tb), np.int32)
        desc[2] = self.max_len
        for t, (tok, sl, pos) in enumerate(entries):
            desc[0, t] = tok
            desc[1, t] = sl
            desc[2, t] = pos
        meta = np.concatenate(
            [self.slot_len, sample_idx, sample_mask.astype(np.int32), pack_slots]
        )
        # only the slots SAMPLED by this pack pick the compiled variant —
        # mid-prefill neighbours don't widen the dispatch; an all-greedy
        # pack reuses the cached zero sampler rows (dead in smode 0)
        smode = max(
            (self.slot_req[i]._smode for i in completed), default=SMODE_GREEDY
        )
        if smode:
            # refresh the RESIDENT rows (once): the fused decode chunk in
            # this same iteration — and _flush_events — reuse them instead
            # of re-building and re-uploading identical arrays
            if not self._sp_fresh:
                self._put_sp(*self._sp_rows())
            spf, spi, btok, bval = self._spf, self._spi, self._btok, self._bval
        else:
            spf, spi, btok, bval = self._sp0

        if self.paged:
            toks, self._last_tok, self._cur_len, self.cache = (
                self._packed_paged(
                    self.params, self.cache, self._flush_btab(),
                    self._last_tok,
                    self.backend.put_host(desc), self.backend.put_host(meta),
                    spf, spi, btok, bval,
                    smode=smode,
                )
            )
        else:
            toks, self._last_tok, self._cur_len, self.cache = (
                self._packed(
                    self.params, self.cache, self._last_tok,
                    self.backend.put_host(desc), self.backend.put_host(meta),
                    spf, spi, btok, bval,
                    smode=smode,
                )
            )
        stats.ticks += 1

        if completed:
            if self.paged and self.prefix is not None:
                # the prompt's K/V now exists in this slot's blocks (the
                # dispatch above is ordered before any future reader) —
                # register its full prompt blocks so later requests skip
                # them. Insert BEFORE any instant finish below: the tree
                # takes its own references, so the blocks outlive the
                # request until evicted.
                for i in completed:
                    self.prefix.insert(
                        self.slot_req[i].prompt, self._slot_blocks[i]
                    )
            items = []
            for i in completed:
                req = self.slot_req[i]
                req.n_generated += 1  # the request's first token (not counted
                items.append((i, req, True))  # in total_tokens, like legacy)
            pending.append(("packed", toks, items, stats))
            for i in completed:
                req = self.slot_req[i]
                # no capacity check: admission guarantees prompt < max_len,
                # so one decode write at position len(prompt) always fits
                if req.n_generated >= req.params.max_new:
                    self._finish(req, i, stats)

    def _chunk_tick(self, stats: ServeStats, pending: deque, active: list[int]) -> None:
        """One fused multi-step decode chunk: as long as no active slot can
        count-finish inside the chunk, k decode steps are one dispatch
        (bucketed to powers of two ≤ ``max_chunk`` so few tick variants
        compile). Stop tokens cannot participate here — the host never
        waits on values — so a stop-terminated slot overruns by at most
        one chunk, discarded at credit time."""
        rem = min(
            min(
                self.slot_req[i].params.max_new - self.slot_req[i].n_generated,
                self.max_len - 1 - int(self.slot_len[i]),
            )
            for i in active
        )
        cap = max(1, min(rem, self.max_chunk))
        k = 1
        while k * 2 <= cap:
            k *= 2
        smode = max(self.slot_req[i]._smode for i in active)
        lanes = self._flush_events()
        if self.paged:
            toks, self._last_tok, self._cur_len, self.cache = (
                self._tick_paged(
                    self.params, self.cache, self._flush_btab(),
                    self._last_tok, self._cur_len,
                    lanes, self._spf, self._spi, self._btok, self._bval,
                    n_steps=k, smode=smode,
                )
            )
        else:
            toks, self._last_tok, self._cur_len, self.cache = (
                self._tick(
                    self.params, self.cache, self._last_tok, self._cur_len,
                    lanes, self._spf, self._spi, self._btok, self._bval,
                    n_steps=k, smode=smode,
                )
            )
        stats.ticks += k
        pending.append(("chunk", toks, [(i, self.slot_req[i]) for i in active], stats))
        # bookkeeping needs only COUNTS — token values are harvested a
        # chunk later, overlapping this chunk's device compute
        for i in active:
            req = self.slot_req[i]
            self.slot_len[i] += k
            req.n_generated += k
            stats.total_tokens += k
            if req.n_generated >= req.params.max_new or self.slot_len[i] + 1 >= self.max_len:
                self._finish(req, i, stats)

    def _spec_depth(self, slot: int) -> int:
        """Adaptive proposal depth for one slot, from its acceptance EWMA.
        Host-side and bucketed to the compiled {1, 2, 4, .., spec_k} depth
        zoo, so adapting never compiles a new program.  With adaptation
        off every slot always proposes the full ``spec_k``."""
        if not self.spec.adaptive:
            return self.spec_k
        e = self._spec_ewma[slot]
        for thresh, d in ((0.7, 8), (0.45, 4), (0.2, 2)):
            if e >= thresh:
                return min(d, self.spec_k)
        return 1

    def _spec_tick(self, stats: ServeStats) -> None:
        """One draft-and-verify iteration over every decoding slot: drain
        the harvest (the drafter reads committed VALUES, and commit counts
        are value-dependent — speculation deliberately trades the
        one-behind pipeline for multi-token commits per dispatch), draft
        per-slot proposals, run ONE packed verify dispatch, then commit
        the accepted prefixes through the standard credit path.

        Depth is capped at ``rem - 1`` (rem = the slot's remaining token
        budget, the same bound :meth:`_chunk_tick` uses) so a commit can
        never overshoot ``max_new``/``max_len`` — count-based finish
        detection stays exact, and every verify-row position stays inside
        the dense row / reserved paged table.  Stop tokens are detected in
        the credit path as always; values past the stop are refunded and
        the slot is released at the next iteration — with the bonus
        sampled token and the exact-match rule, a speculated stream stops
        at exactly the token the sequential engine would have stopped
        at."""
        self._drain_pending()
        self._release_stopped(stats)
        decoding = [
            i for i, r in enumerate(self.slot_req)
            if r is not None and self.slot_fed[i] >= len(r.prompt)
        ]
        if not decoding:
            return
        b = self.B
        depths = np.zeros(b, np.int32)
        ctxs: list[Optional[np.ndarray]] = [None] * b
        for i in decoding:
            r = self.slot_req[i]
            rem = min(
                r.params.max_new - r.n_generated,
                self.max_len - 1 - int(self.slot_len[i]),
            )
            d = min(self.spec_k, rem - 1, self._spec_depth(i)) if r._spec else 0
            depths[i] = max(d, 0)
            if depths[i] > 0:
                ctxs[i] = np.concatenate(
                    [
                        np.asarray(r.prompt, np.int64),
                        np.asarray(r.generated, np.int64),
                    ]
                )
        if depths.any():
            props = self.drafter.propose(ctxs, depths)
            for i in decoding:
                depths[i] = min(int(depths[i]), len(props[i]))
        else:
            props = [[] for _ in range(b)]
        kmax = max(1, int(depths.max()))
        depth_k = 1
        while depth_k < kmax:
            depth_k *= 2
        w = depth_k + 1
        # slot-major verify rows [last_token, draft_1 .. draft_d]; rows
        # past a slot's depth (and whole inactive slots) carry the
        # position sentinel — scatter dropped, acceptance depth-masked.
        # desc and meta share ONE upload (see _spec_fn): pack[:, :b*w] is
        # the descriptor, pack[:, b*w:] the per-slot (depth, active, cl)
        pack = np.zeros((3, b * w + b), np.int32)
        desc = pack[:, : b * w]
        meta = pack[:, b * w :]
        desc[2] = self.max_len
        for i in decoding:
            r = self.slot_req[i]
            d = int(depths[i])
            cl = int(self.slot_len[i])
            r0 = i * w
            desc[0, r0] = r.generated[-1]
            if d:
                desc[0, r0 + 1 : r0 + 1 + d] = props[i][:d]
            desc[1, r0 : r0 + 1 + d] = i
            desc[2, r0 : r0 + 1 + d] = cl + np.arange(d + 1)
            meta[0, i] = d
            meta[1, i] = 1
            meta[2, i] = cl
        smode = max(self.slot_req[i]._smode for i in decoding)
        if smode:
            if not self._sp_fresh:
                self._put_sp(*self._sp_rows())
            spf, spi, btok, bval = self._spf, self._spi, self._btok, self._bval
        else:
            spf, spi, btok, bval = self._sp0
        if (depth_k, smode) not in self._spec_shapes:
            self._spec_shapes.add((depth_k, smode))
            stats.prefill_compiles += 1
        if self.paged:
            targets, commit, self._last_tok, self._cur_len, self.cache = (
                self._spec_prog_paged(
                    self.params, self.cache, self._flush_btab(),
                    self._last_tok, self._cur_len,
                    self.backend.put_host(pack),
                    spf, spi, btok, bval, depth_k=depth_k, smode=smode,
                )
            )
        else:
            targets, commit, self._last_tok, self._cur_len, self.cache = (
                self._spec_prog(
                    self.params, self.cache, self._last_tok, self._cur_len,
                    self.backend.put_host(pack),
                    spf, spi, btok, bval, depth_k=depth_k, smode=smode,
                )
            )
        stats.ticks += 1
        stats.spec_ticks += 1
        # value-blocking by design (see above); ONE transfer for both
        t_h, c_h = jax.device_get((targets, commit))
        now = time.perf_counter()
        for i in decoding:
            r = self.slot_req[i]
            c = int(c_h[i])  # accepted run + the bonus token, >= 1
            d = int(depths[i])
            stats.spec_proposed += d
            stats.spec_accepted += c - 1
            if self.spec.adaptive and d > 0:
                self._spec_ewma[i] = 0.5 * self._spec_ewma[i] + 0.5 * (
                    (c - 1) / d
                )
            r.n_generated += c
            self.slot_len[i] += c
            stats.total_tokens += c
            for j in range(c):
                self._credit(r, int(t_h[i, j]), now, stats)
            self._stamp(r, now)
            if r.finish_reason is None and (
                r.n_generated >= r.params.max_new
                or self.slot_len[i] + 1 >= self.max_len
            ):
                self._finish(r, i, stats)

    # ------------------------------------------------------------------- run

    def _service_once(self, stats: ServeStats, admit: bool = True) -> bool:
        """ONE scheduling iteration — the unit both ``run()`` and the
        streaming ``step()`` are built from: apply cancellations, release
        stop-finished slots, admit, dispatch this iteration's fused
        tick(s), then harvest everything older than the newest in-flight
        dispatch. Returns whether any work remains.

        ``admit=False`` drains in-flight slots without pulling from the
        waiting queue — the controlled-run slice boundary (a cluster about
        to reconfigure wants idle slots, not an empty queue)."""
        self._apply_cancels(stats)
        self._release_stopped(stats)
        if not admit:
            pass
        elif self.paged:
            self._admit_paged(stats)
        elif self.unified:
            self._admit_unified(stats, self._pending)
        else:
            self._admit(stats)
        stats.kv_bytes_resident = max(
            stats.kv_bytes_resident, self.kv_bytes_resident()
        )
        active = [i for i, r in enumerate(self.slot_req) if r is not None]
        if not active:
            self._drain_pending()
            self._release_stopped(stats)
            return bool(self.waiting) or any(
                r is not None for r in self.slot_req
            )
        if self.unified and self._prefilling:
            # merge mode: one ragged prefill pack, and — in the same
            # loop iteration — a fused decode chunk for every decoding
            # slot (including one whose prompt just completed in this
            # very pack). Admission never stalls decode.
            self._packed_tick(stats, self._pending)
            if self.spec is not None:
                self._spec_tick(stats)
            else:
                decoding = [
                    i for i, r in enumerate(self.slot_req)
                    if r is not None and self.slot_fed[i] >= len(r.prompt)
                ]
                if decoding:
                    self._chunk_tick(stats, self._pending, decoding)
        elif self.spec is not None:
            self._spec_tick(stats)
        else:
            self._chunk_tick(stats, self._pending, active)
        while len(self._pending) > 1:
            self._harvest(self._pending.popleft())
        return True

    def kv_bytes_resident(self) -> int:
        """Actual HBM bytes of KV state resident right now, dtype-aware.
        Dense engines report the constant cache allocation (every slot's
        worst case is always resident); paged engines report used blocks
        times the measured per-block weight — which is how an int8 pool
        shows ~4x the requests in the same byte budget."""
        if self.paged:
            return self.pool.used * self.pool.bytes_per_block
        return self._dense_kv_bytes

    @property
    def stream_stats(self) -> ServeStats:
        """Stats accumulated by step()-driven serving (handle iterators,
        inline cancellations) — work served OUTSIDE any ``run()`` window.
        A complete picture of a mixed streamed+drained session is this
        plus the ServeStats each ``run()`` returned."""
        return self._stream_stats

    def step(self) -> bool:
        """Advance the engine by one scheduling iteration (the streaming
        driver: a ``RequestHandle`` iterator calls this when no run loop
        owns the engine). Returns whether any work remains."""
        with self._drive_lock:
            busy = self._service_once(self._stream_stats)
            if not busy:
                self._drain_pending()
                self._release_stopped(self._stream_stats)
            return busy

    def _handle_pump(self, req: Request) -> None:
        """Make progress on behalf of a blocked handle iterator: drive the
        engine when this thread owns it, politely poll when a controller
        thread (cluster split mode) does. A poisoned (declared-dead)
        engine is never driven: the handle polls until the cluster has
        re-homed its request onto a survivor."""
        if self._running or self._poisoned:
            time.sleep(1e-4)
            return
        if self.step():
            return
        self._apply_cancels(self._stream_stats)
        if not req.complete:
            raise RuntimeError(
                f"engine idle but request {req.rid} incomplete — "
                "was it submitted to this engine?"
            )

    def run(
        self, arrivals=None, *, deadline_s=None, on_tick=None, gate=None
    ) -> ServeStats:
        """Drain all submitted requests; returns throughput + latency stats.

        ``arrivals`` optionally simulates an open-loop request stream: an
        iterable of ``(t_offset_seconds, Request)`` submitted once the run
        clock passes each offset (mixed-arrival benchmarking).

        ``gate`` is the admission hook for arrival-stream requests: called
        with each due request BEFORE it joins the queue, it may raise
        :class:`AdmissionRejected` — the request then finishes immediately
        as ``"rejected"`` (an open-loop stream has no caller to raise
        into). Gating happens at the scheduled arrival time against the
        live queue, which is what makes deadline-based shedding honest: a
        burst is rejected as the queue grows, not waved through because
        the queue was empty when the batch was handed over.

        ``deadline_s`` bounds the run to a control interval: once the run
        clock passes it, admission stops and the loop exits as soon as the
        in-flight slots drain — requests still waiting stay queued for the
        next run (the cluster's controlled-serving slice boundary, which
        leaves the engine reconfigure()-safe: idle slots, non-empty queue).

        ``on_tick`` is called once per scheduling iteration OUTSIDE the
        drive lock — the cluster's watchdog heartbeat and the test-only
        fault-injection point. After each call the poison pill is checked:
        a replica declared dead aborts here, at an iteration boundary,
        without touching re-homed state."""
        stats = ServeStats()
        self._done_now = []
        alloc_fail0 = self.pool.alloc_failures if self.paged else 0
        t0 = time.perf_counter()
        arr: deque = deque(
            sorted(arrivals, key=lambda a: a[0]) if arrivals else ()
        )
        with self._cancel_lock:  # see cancel(): no inline apply may overlap
            self._running = True
        try:
            while True:
                if on_tick is not None:
                    on_tick()
                if self._poisoned:
                    break
                now = time.perf_counter() - t0
                while arr and arr[0][0] <= now:
                    t_off, req = arr.popleft()
                    if gate is not None:
                        try:
                            gate(req)
                        except AdmissionRejected as rej:
                            req.finish_reason = "rejected"
                            req.reject_reason = rej.reason
                            req.submitted_at = t0 + t_off
                            req.done_at = time.perf_counter()
                            self.finished.append(req)
                            continue
                    self.submit(req)
                    # the TTFT clock starts at the SCHEDULED arrival, not at
                    # whenever the loop got around to polling the deque —
                    # otherwise time spent inside a blocking dispatch hides
                    # queueing delay from the latency stats
                    req.submitted_at = t0 + t_off
                stats.queue_peak = max(stats.queue_peak, len(self.waiting))
                expired = deadline_s is not None and now >= deadline_s
                if not (
                    any(r is not None for r in self.slot_req)
                    or (self.waiting and not expired)
                    or arr
                    or self._cancels
                ):
                    break
                with self._drive_lock:  # serialize vs inline cancel/step()
                    busy = self._service_once(stats, admit=not expired)
                if not busy and arr:
                    # idle until the next scheduled arrival
                    wait = arr[0][0] - (time.perf_counter() - t0)
                    if wait > 0:
                        time.sleep(min(wait, 0.001))
            if not self._poisoned:
                with self._drive_lock:
                    self._drain_pending()
                    self._release_stopped(stats)
        finally:
            with self._cancel_lock:
                self._running = False
        stats.wall_seconds = time.perf_counter() - t0
        stats.kv_bytes_resident = max(
            stats.kv_bytes_resident, self.kv_bytes_resident()
        )
        if self.paged:
            stats.alloc_failures = self.pool.alloc_failures - alloc_fail0
        for req in self._done_now:
            if req.first_token_at is not None:
                stats.ttfts.append(req.first_token_at - req.submitted_at)
                if req.done_at is not None and req.n_generated >= 2:
                    stats.tpots.append(
                        max(req.done_at - req.first_token_at, 0.0)
                        / (req.n_generated - 1)
                    )
        return stats
