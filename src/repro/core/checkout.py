"""Batched checkout engine — the default multi-version retrieval path.

Data-flow map (kernels -> core -> query/serve)::

    request: vids = [v0, v1, ... v_{K-1}]          (query layer, serve layer)
      └─ superblock                                core.checkout (this module)
      │    get_superblock concatenates every partition's block into ONE
      │    (ΣR_p, D) array (segments BN-aligned, D padded to 128 lanes),
      │    cached on the store keyed by ``store.epoch`` — repeated waves
      │    reuse the device-resident copy and skip the host→device transfer
      └─ plan_wave                                 [host, vectorized numpy]
      │    rebases each version's LOCAL rlist by its partition's row offset,
      │    so one flat adaptive (starts, mode) tile plan (plan_batched)
      │    covers versions from DIFFERENT partitions back to back; emits a
      │    per-tile ``hi`` bound (partition segment end) that lets
      │    consecutive tail chunks promote to run DMAs
      └─ one fused gather for the WHOLE wave
      │    device path:  kernels.ops.checkout_wave — one pallas_call per
      │                  SMEM-sized slice of the plan (``kernels.plan_launch``;
      │                  usually one), all writing one output, no matter
      │                  how many partitions the wave touches (run
      │                  DMAs where the rlist is dense, row DMAs where
      │                  scattered; the ``hi`` bound is checked on device),
      │                  launched at a tile count from a fixed ladder
      │                  (``ops.launch_tiles``) so waves share compiles
      │    host path:    one np.take over the rebased concatenation when a
      │                  superblock is already cached; per-partition np.takes
      │                  otherwise (numpy pays no launch cost, so host-only
      │                  processes skip the superblock copy entirely)
      └─ reassemble per-version blocks in request order
           ``device_out=True`` DEFERS this last hop: the wave comes back as
           a ``WaveResult`` handle holding the device-resident packed
           gather plus its split plan (host/perpart tiers: pre-materialized
           blocks behind the same handle) — ``materialize()`` performs the
           device→host transfer and the per-version split later, so the
           serve layer can DISPATCH wave N+1 (plan + launch) while wave N
           is still in flight and run N's host split under N+1's kernel
           (``serve.checkout.BatchedCheckoutServer``'s dispatch/deliver
           pipeline)

``checkout_partitioned`` routes through this wave engine by default; the
previous one-gather-PER-PARTITION path survives as
``checkout_partitioned_perpart`` (the oracle and benchmark baseline), and
``checkout_versions_loop`` is the seed per-version gather loop.

Commit ingest waves — the write-side twin (``core.partition`` +
this module)::

    commits: K = [{rlist|table, parent, pid}, ...]   (serve write tickets)
      └─ PartitionedCVD.commit_many                  core.partition
      │    STAGE: per-commit delta extraction (the sorted-join
      │    ``datamodels.diff_against_parents`` for table-form commits —
      │    parents may be staged earlier in the SAME wave), then ONE bulk
      │    CSR/assignment/data append and ONE ``build_partition`` per
      │    touched label (not per commit); everything before the journal
      │    append is side-effect-free
      └─ journal group commit                        core.journal
      │    ONE ``commit.batch`` record + ONE fsync covers the whole wave;
      │    replay applies all K commits or none (all-or-nothing, same
      │    kill-matrix contract as single commits)
      └─ COMMIT: pure field swaps + one epoch bump   core.partition
      └─ refresh_superblocks_after_commit            (this module)
           targeted device-state maintenance instead of the old
           nuke-every-superblock: pinned groups the wave did NOT touch
           revalidate at the new epoch in place (zero work; they stay
           pinned unless a grown group needs their bytes);
           touched superblocks extend IN PLACE via
           ``extend_superblock_after_commit`` — the
           ``kernels.ops.segment_append`` kernel (one pallas_call per
           SMEM-sized slice of the tile plan) reuses every
           untouched BN-aligned tile device-to-device (sel 0), uploads
           only the new tiles (sel 1) and zero-fills alignment slack on
           device (sel 2), so an ingest wave's host→device traffic is
           bounded by the new rows, not the store size

Telemetry -> trigger -> migration loop (the online-repartitioning half,
paper §4.3)::

    checkout_wave                                  (every wave, this module)
      └─ DensityStats                              [host accumulator on store]
      │    once an accumulator is attached (RepartitionTrigger attaches
      │    one; unmonitored stores pay nothing) every planned wave records
      │    per-vid run density and tile counts (kernel path: straight off
      │    ``plan_wave``'s plan; host path: ``measure_density`` over the
      │    same rlists) — sustained row-DMA-dominated waves grow
      │    ``low_streak``
      └─ core.online.RepartitionTrigger            [between serve flushes]
      │    low_streak >= min_waves -> run LYRESPLIT on the version tree,
      │    emit a ``core.partition.MigrationPlan`` (explicit move/insert
      │    segments + intelligent-vs-naive cost) when the new partitioning
      │    is worth adopting
      └─ PartitionedCVD.apply_migration(plan)      [host, in place]
      │    morphs the partition blocks segment-by-segment (old blocks are
      │    the move source, base data only for genuinely new rows), bumps
      │    the epoch and EAGERLY evicts the stale superblock cache
      └─ migrate_superblock(store, old_sb, plan)   [device, incremental]
           rebuilds the superblock with ``kernels.ops.segment_move`` (one
           pallas_call per SMEM-sized slice of the tile plan, all writing
           one output): untouched BN-aligned tiles are device-to-device
           copies from the OLD superblock (never re-crossing the host link);
           only changed tiles ride a small host-uploaded delta — the
           intelligent-migration analogue of Figs 14-15, applied to the
           device-resident serve cache

``get_superblock`` also takes an optional ``max_bytes`` budget: a store
whose ΣR×D superblock would exceed it refuses to pin the whole-store copy
— but over-budget stores do NOT lose fusion.  The partition-group layer
(budget-aware partial fusion)::

    over-budget wave                               core.checkout (this module)
      └─ SuperblockGroups                          [store-level group cache]
      │    the partition set is packed into budget-fitting GROUPS, hot
      │    partitions first (``core.online.HotSetPolicy``: per-partition
      │    wave-touch EWMA blended with the per-vid run-density EWMA from
      │    ``DensityStats``); each group gets its own ``Superblock`` over
      │    just its partitions (same BN/lane-tile layout, a ``pids`` slot
      │    map instead of the identity), pinned ON DEMAND under the shared
      │    ``max_bytes`` budget with LRU eviction of cold groups
      └─ _grouped_wave                             [wave routing/splitting]
      │    the wave's vids split by group; each TOUCHED PINNED group runs
      │    as ONE fused ``checkout_wave`` gather over that group's
      │    superblock (launches == touched pinned groups; a gather is one
      │    pallas_call per SMEM-sized plan slice); only genuinely
      │    unpinned stragglers (partitions bigger than the whole budget, or
      │    groups the LRU could not co-pin this wave) route through the
      │    per-partition engine
      └─ migration: an epoch bump migrates or evicts PER GROUP —
           ``PartitionedCVD.apply_migration`` detaches the pinned group
           superblocks (device copies intact), morphs the store, then
           ``migrate_groups`` maps each group's partitions through
           ``plan.matched_old`` and replays ``migrate_superblock`` per
           group (device tiles reused, delta-only upload) instead of
           nuking the whole cache

The single-superblock fast path is the one-group degenerate case: a store
whose full superblock fits the budget (or has none) never builds the group
layer, and its wave path is unchanged.  The grouping itself is
self-correcting: every ``auto_regroup_every`` group waves,
``SuperblockGroups.maybe_regroup`` compares the LIVE hot ranking against
the prefix the plan packed around and re-forms the groups when the served
hot set drifted (one tenant's shifted traffic cannot permanently pin
another tenant's now-cold groups out of budget).

Multi-tenant serve + epoch read leases (``serve/tenancy.py`` over
``core/faults.py``)::

    tenants ── submit(tenant, vid) ──┐   serve.tenancy.MultiTenantServer
      │   admission control: per-tenant quotas (inflight tickets, wave
      │   share, pinned-byte share) + a bounded global backlog — breaching
      │   either SHEDS explicitly (``QuotaExceeded``/``Overloaded`` to the
      │   caller) instead of queueing unboundedly
      └─ deficit-round-robin scheduler          [fair cross-tenant waves]
      │    each round every backlogged tenant earns ``wave_share`` deficit
      │    and spends it in granted waves, so a burst tenant cannot starve
      │    the rest; grants run on per-tenant worker threads, each wave a
      │    ``BatchedCheckoutServer.flush`` serialized under the store lock
      │    (delivery joins run OUTSIDE it — tenant A's host split overlaps
      │    tenant B's dispatch)
      └─ per-wave ``core.faults.ReadLease``      [epoch-consistent reads]
      │    every dispatched wave leases the epoch it planned against (the
      │    lease total mirrors onto ``store._inflight_waves``); a wave
      │    admitted at epoch E delivers against epoch-E superblocks even
      │    while a migration lands
      └─ migration drain                        [coordinator rounds]
           the coordinator's ``RepartitionTrigger`` runs with
           ``drain_timeout_s`` set: ``EpochReadLeases.draining`` blocks
           NEW leases at the current epoch, waits for in-flight waves to
           deliver, then migrates — draining leases instead of racing
           them (or deferring when stragglers outlast the timeout).

Failure-site catalogue + recovery invariants (``core.faults``)::

    every stateful step above carries a named ``fault_point`` — a no-op
    until a deterministic ``FaultPlan`` is armed — so the recovery tests
    (and the CI ``REPRO_FAULT_SEED`` matrix) can exercise each failure
    mode on purpose instead of waiting for it.  22 catalogued fault
    sites (``core.faults.SITES``; count checked against the catalogue by
    ``tools.analyze`` rule REPRO001):

      superblock.upload   Superblock.device(): fires BEFORE the transfer —
                          ``_device`` stays None, a retry re-uploads
      wave.launch         _gather_off_superblock: fires after planning,
                          before the pallas_call — plan memo intact, a
                          retry replans from cache and relaunches
      group.pin           SuperblockGroups.pin: fires before the build —
                          no bytes pinned, LRU state unchanged
      group.evict         SuperblockGroups._evict: fires before the pop —
                          the victim stays pinned and accounted
      serve.transfer      _WavePart.split: fires before the device→host
                          copy — the device handle survives for the retry
      migrate.superblock  migrate_superblock entry — the old superblock is
                          still whole; callers degrade to a lazy rebuild
      serve.dispatch / serve.delivery / online.trigger / migration.commit
                          live in serve/checkout.py, core/online.py and
                          core/partition.py (see their docstrings)
      serve.admit         MultiTenantServer.submit: fires before any
                          admission state changes — the caller retries,
                          nothing was queued or counted
      serve.shed          fires before a shed is recorded/raised — the
                          shed decision itself stays deterministic
      tenant.preempt      the DRR scheduler ending a backlogged tenant's
                          turn — accounting only, grants already issued
                          are unaffected
      lease.expire        EpochReadLeases.draining entry — nothing blocked
                          or drained yet; the migration defers and the
                          density streak survives for the retry
      ingest.extract      PartitionedCVD.commit_many entry — nothing
                          staged, nothing durable; a plain retry restages
                          the whole wave from scratch
      ingest.commit       commit_version/commit_many at the stage→journal
                          boundary — store AND journal both untouched, so
                          a retry re-stages and re-appends cleanly
      ingest.append       extend_superblock_after_commit entry — the old
                          superblock (host + device) is still whole; the
                          refresh degrades to evicting just that group,
                          which rebuilds lazily on next touch
      journal.append      core.journal.Journal.append: fires before any
                          bytes are written — data-plane appends run
                          BEFORE the in-memory swap, so nothing mutated
                          and a plain retry is safe
      journal.fsync       between the buffered frame write and its fsync —
                          the repair truncates the unacknowledged frame,
                          the retry appends it clean
      journal.replay      journal.replay_into entry, before any record is
                          applied — a retried restore() replays from the
                          same verified snapshot
      disk.torn_write /   Journal._write_frame: a half/corrupted frame
      disk.bitflip        hits disk FIRST, then the fault raises — the
                          in-process repair (or, after a kill, the
                          reader's first-bad-record truncation) removes it

    The invariants every site is placed to preserve (and the fault suite
    asserts): a fault leaves no half-applied state — pins/evictions stay
    balanced (``pins - evictions == len(groups)``), no device buffer leaks
    (every detached superblock's ``_device`` is released on every failure
    path), ``store._inflight_waves`` (a ``core.faults.GuardedCounter``)
    never underflows, per-epoch lease and per-tenant quota/pin accounting
    balances to zero after ``close()``, and a retried/degraded wave
    delivers results bit-identical to the fault-free run — per tenant,
    even under contention.

Crash-recovery contract (``core.durability`` + ``core.journal``)::

    snapshot   every SNAP_EVERY waves: graph/data/assignment bitexact +
               maintenance-loop meta, parent-chained for content dedup,
               per-leaf crc32 digests in the checkpoint manifest
    journal    every store mutation BETWEEN snapshots appends a framed,
               checksummed record to journal-<snapshot_vid>.wal; commit/
               migration records fsync before the in-memory swap (an op
               that returned survives any crash — RPO 0), watermark/
               layout records ride buffered (advisory)
    restore    newest snapshot whose digests verify (falling back along
               the parent chain past corrupt generations), then replay
               of every newer generation's journal — truncated at the
               first torn/bad record, idempotent by epoch/vid guards
    scrub      offline integrity pass: recompute every generation's leaf
               digests + every journal's record checksums; detection
               only, restore() does the healing
"""
from __future__ import annotations

import collections
import concurrent.futures
import dataclasses
import functools
import logging
import os
import time
from typing import ClassVar, Optional, Sequence

import numpy as np

from .. import obs
from .faults import fault_point
from .graph import BipartiteGraph

logger = logging.getLogger(__name__)


@functools.lru_cache(maxsize=1)
def _default_use_kernel() -> bool:
    """Backend probe, resolved ONCE per process (importing jax and asking
    for the default backend on every checkout call is measurable on the
    serve hot path)."""
    import jax
    return jax.default_backend() == "tpu"


def _fused_host_gather(data: np.ndarray, rlists: Sequence[np.ndarray]
                       ) -> list[np.ndarray]:
    """One gather for the whole wave: concatenate rlists, single np.take,
    split back by offsets (zero-copy views)."""
    if not rlists:
        return []
    offs = np.cumsum([0] + [len(rl) for rl in rlists])
    if offs[-1] == 0:
        return [data[:0] for _ in rlists]
    packed = data.take(np.concatenate(rlists), axis=0)
    return [packed[offs[i]:offs[i + 1]] for i in range(len(rlists))]


def checkout_rlists(data: np.ndarray, rlists: Sequence[np.ndarray], *,
                    use_kernel: Optional[bool] = None,
                    stages: Optional["WaveStages"] = None
                    ) -> list[np.ndarray]:
    """Materialize K rlists from one data block in a single fused pass.

    use_kernel: True -> Pallas ``checkout_batched`` (ONE kernel launch;
    interpret mode off-TPU), False -> fused host gather, None -> kernel on
    TPU, host otherwise (probe cached per process).  The kernel adds the
    bytes it uploads to ``stages.h2d_bytes``.
    """
    if use_kernel is None:
        use_kernel = _default_use_kernel()
    if not use_kernel:
        return _fused_host_gather(np.asarray(data), rlists)
    from ..kernels import ops as K
    outs, _ = K.checkout_batched(data, rlists, stages=stages)
    return outs


def checkout_versions(graph: BipartiteGraph, data: np.ndarray,
                      vids: Sequence[int], *,
                      use_kernel: Optional[bool] = None) -> list[np.ndarray]:
    """Batched checkout straight off a BipartiteGraph (unpartitioned CVD)."""
    return checkout_rlists(data, [graph.rlist(int(v)) for v in vids],
                           use_kernel=use_kernel)


# ------------------------------------------------------ density telemetry --

@dataclasses.dataclass
class DensityStats:
    """Per-store accumulator of wave gather-mode telemetry.

    Every planned wave records, per requested vid, the measured run density
    (fraction of BN-row chunks whose rids are consecutive — the fraction of
    the wave the kernel can serve with run DMAs instead of BN row DMAs).
    ``low_streak`` counts CONSECUTIVE waves whose aggregate density fell
    below ``low_threshold``; ``core.online.RepartitionTrigger`` consumes the
    streak as the repartition signal.
    """
    low_threshold: float = 0.5
    ewma_alpha: float = 0.5
    waves: int = 0                 # all-time planned waves
    tiles: int = 0                 # all-time tiles planned
    run_tiles: float = 0.0         # all-time density-weighted tiles
    low_streak: int = 0            # consecutive row-DMA-dominated waves
    last_wave_density: float = 1.0
    per_vid: dict = dataclasses.field(default_factory=dict)  # vid -> EWMA

    def record(self, vids: Sequence[int], densities: np.ndarray,
               tiles_per_vid: np.ndarray) -> None:
        densities = np.asarray(densities, np.float64)
        tiles_per_vid = np.asarray(tiles_per_vid, np.int64)
        t = int(tiles_per_vid.sum())
        self.waves += 1
        if t == 0:
            return          # no gather happened: no evidence either way —
                            # an all-empty wave must not break a streak
        runs = float((densities * tiles_per_vid).sum())
        self.tiles += t
        self.run_tiles += runs
        wave_d = runs / t
        self.last_wave_density = wave_d
        if wave_d < self.low_threshold:
            self.low_streak += 1
        else:
            self.low_streak = 0
        a = self.ewma_alpha
        for v, d in zip(vids, densities):
            prev = self.per_vid.get(int(v))
            self.per_vid[int(v)] = float(d) if prev is None \
                else (1.0 - a) * prev + a * float(d)

    @property
    def mean_density(self) -> float:
        return self.run_tiles / self.tiles if self.tiles else 1.0

    def reset(self) -> None:
        """Post-repartition: stale signal — the streak and the per-vid
        EWMAs describe the OLD layout.  All-time counters survive."""
        self.low_streak = 0
        self.last_wave_density = 1.0
        self.per_vid.clear()


def get_density_stats(store, *, create: bool = False
                      ) -> Optional[DensityStats]:
    """The store's DensityStats accumulator (attached like the superblock
    cache; None when absent and ``create`` is False or the store forbids
    attributes)."""
    stats = getattr(store, "_density_stats", None)
    if stats is None and create:
        stats = DensityStats()
        try:
            store._density_stats = stats
        except AttributeError:
            return None
    return stats


def measure_density(rlists: Sequence[np.ndarray], block_n: int, *,
                    density_threshold: float = 0.05
                    ) -> tuple[np.ndarray, np.ndarray]:
    """(density, tiles) per rlist — the fraction of BN-row tiles the wave
    engine would serve with a run DMA, without building a plan (host-path
    telemetry).  Mirrors the planner end to end so every tier records the
    same number for the same wave: ``plan_batched``'s run classification
    AND its below-threshold demotion first, then ``plan_wave``'s tail
    promotion (a ragged final chunk whose valid rids are consecutive is ONE
    run DMA, so a dense version shorter than a tile measures 1.0)."""
    dens = np.ones(len(rlists), np.float64)
    tiles = np.zeros(len(rlists), np.int64)
    for k, rl in enumerate(rlists):
        rl = np.asarray(rl, np.int64)
        n = len(rl)
        t = -(-n // block_n) if n else 0
        tiles[k] = t
        if not t or block_n <= 1:
            continue
        pad = t * block_n - n
        padded = np.concatenate([rl, np.full(pad, rl[-1], np.int64)]) if pad \
            else rl
        chunks = padded.reshape(t, block_n)
        runs = np.all(np.diff(chunks, axis=1) == 1, axis=1)
        if runs.mean() < density_threshold:
            runs = np.zeros(t, bool)
        tail = rl[(t - 1) * block_n:]
        if len(tail) < block_n and (len(tail) <= 1
                                    or np.all(np.diff(tail) == 1)):
            runs[-1] = True
        dens[k] = float(runs.mean())
    return dens, tiles


def _plan_mode_density(plan) -> tuple[np.ndarray, np.ndarray]:
    """(density, tiles) per version off a PLANNED wave: the fraction of its
    tiles actually going out as run DMAs (mode 1) — post tail-promotion,
    post threshold — i.e. what the kernel will really do."""
    tiles = np.diff(plan.tile_offsets)
    dens = np.ones(len(tiles), np.float64)
    for k in range(len(tiles)):
        if tiles[k]:
            t0, t1 = int(plan.tile_offsets[k]), int(plan.tile_offsets[k + 1])
            dens[k] = float(plan.mode[t0:t1].mean())
    return dens, tiles


# ------------------------------------------------------------- wave results --

_wave_executor: Optional[concurrent.futures.ThreadPoolExecutor] = None

DEFER_MIN_TILES = 128   # worker-thread launches only for waves at least this
                        # big: two GIL-contended thread handoffs cost more
                        # than a tiny kernel hides
WAVE_WORKER_ENV = "REPRO_WAVE_WORKER"   # "1" opts inline-dispatch backends
                                        # into worker-thread launches


def _defer_via_worker(n_tiles: int) -> bool:
    """Should a deferred (device_out) launch ride the worker thread?

    On TPU never: the jitted call already returns with the kernel in
    flight (JAX async dispatch) — a worker adds nothing but handoff
    latency.  On inline-dispatch backends (interpret-mode CPU) the worker
    emulates the in-flight kernel, but the emulation only pays on hosts
    with CPU to spare — python/XLA contention on small machines costs more
    than the overlap buys — so it is OPT-IN via ``REPRO_WAVE_WORKER=1``
    and gated to waves big enough to outweigh the handoffs.  The default
    inline path still defers the device→host transfer and per-ticket
    split (the pipeline's deliver stage); only the kernel itself runs at
    dispatch."""
    from ..kernels.ops import _on_tpu
    if _on_tpu():
        return False
    if os.environ.get(WAVE_WORKER_ENV, "") != "1":
        return False
    return n_tiles >= DEFER_MIN_TILES


def _wave_launcher() -> concurrent.futures.ThreadPoolExecutor:
    """The single-worker executor deferred (``device_out``) kernel gathers
    launch on.

    On a real accelerator JAX async dispatch already returns before the
    kernel finishes, but interpret-mode backends (the CPU emulation) execute
    the pallas_call INLINE at dispatch — launching through the worker gives
    device_out waves the same in-flight semantics everywhere (XLA execution
    releases the GIL, so the caller keeps planning/splitting under the
    running kernel).  ONE worker by design: launches retire in submission
    order, like a device stream, and concurrent waves cannot race the
    backend.  Only the functionally pure jitted call runs here — all store
    mutation (planning, telemetry, superblock pins) stays on the caller's
    thread."""
    global _wave_executor
    if _wave_executor is None:
        _wave_executor = concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="checkout-wave")
    return _wave_executor


@dataclasses.dataclass
class WaveStages:
    """Host seconds and bytes of one read wave, by stage.  The stages never
    nest, so each second lands in exactly one field.  Dispatch fills the
    ``DISPATCH`` fields, the delivery join (``WaveResult.materialize``) the
    ``DELIVERY`` ones; the serve layer sums each half into
    ``serve.checkout.CheckoutStats`` when that half of the wave is done."""
    DISPATCH: ClassVar[tuple] = ("plan_s", "launch_s", "pin_s",
                                 "straggler_s", "h2d_bytes", "tiles",
                                 "pad_tiles")
    DELIVERY: ClassVar[tuple] = ("device_wait_s", "d2h_s", "d2h_bytes")
    plan_s: float = 0.0         # plan_wave_cached, per gather
    launch_s: float = 0.0       # the jitted gather calls: trace, lower and
                                # compile of a new shape, then the enqueue
    pin_s: float = 0.0          # superblock pins: host build, evictions,
                                # first upload
    straggler_s: float = 0.0    # the per-partition straggler batch
    h2d_bytes: int = 0          # superblock uploads + straggler partitions
    tiles: int = 0              # BN-row tiles the launched gathers planned
    pad_tiles: int = 0          # tiles the launch ladder added to those
                                # (``kernels.ops.launch_tiles``)
    device_wait_s: float = 0.0  # blocking until each packed gather is done
    d2h_s: float = 0.0          # device→host copies and per-vid splits
    d2h_bytes: int = 0          # packed gathers copied to the host


@dataclasses.dataclass
class _WavePart:
    """One contiguous gather of a wave: either a still-device-resident
    packed block plus its per-vid split plan, or pre-materialized host
    blocks (host tier, per-partition stragglers).  ``idxs`` are the wave
    positions the part's blocks land in."""
    idxs: Sequence[int]
    mats: Optional[list] = None         # pre-materialized per-idx blocks
    packed: object = None               # device-resident packed gather (a
                                        # jax array, or a Future of one when
                                        # the launch rode _wave_launcher)
    segments: Optional[list] = None     # per-idx row slices of ``packed``
    d: int = 0                          # valid feature width of ``packed``
    width: int = 0                      # padded row width: ``packed`` holds
                                        # lane-rows, width/128 per row
    stages: Optional[WaveStages] = None  # the wave's counters (device parts)

    def split(self) -> list:
        """Force this part to host blocks: join the in-flight launch, ONE
        device→host transfer of the packed gather, then per-vid zero-copy
        views.  The wait for the kernel and the copy are timed apart (the
        copy waits on the kernel anyway, so the split adds no sync)."""
        if self.mats is None:
            # fires BEFORE the transfer consumes anything: the device handle
            # survives an injected failure, so a delivery retry succeeds
            fault_point("serve.transfer")
            t0 = time.perf_counter()
            with obs.span("checkout.device_wait"):
                packed = self.packed
                if isinstance(packed, concurrent.futures.Future):
                    packed = packed.result()
                wait = getattr(packed, "block_until_ready", None)
                if wait is not None:
                    wait()
            t1 = time.perf_counter()
            nbytes = int(packed.nbytes)
            with obs.span("checkout.d2h", bytes=nbytes):
                arr = np.asarray(packed).reshape(-1, self.width)[:, :self.d]
                self.mats = [arr[seg] for seg in self.segments]
            if self.stages is not None:
                self.stages.device_wait_s += t1 - t0
                self.stages.d2h_s += time.perf_counter() - t1
                self.stages.d2h_bytes += nbytes
            self.packed = None          # release the device handle
            self.segments = None
        return self.mats


@dataclasses.dataclass
class WaveResult:
    """Handle to one wave's per-vid results, possibly still in flight.

    The kernel tier's ``checkout_wave(..., device_out=True)`` returns the
    launched pallas_call's output WITHOUT blocking (JAX async dispatch keeps
    the kernel in flight); ``materialize()`` later performs the device→host
    transfer and the per-vid split — the deliver half of the serve
    pipeline.  Host/perpart tiers return pre-materialized blocks through
    the same handle (``ready()`` is immediately True), so callers drive
    every tier identically.  ``materialize()`` is idempotent and caches its
    result; it is bit-identical to the eager (``device_out=False``) path,
    which is literally this handle materialized at once.  ``stages`` holds
    the wave's host seconds and bytes by stage (``WaveStages``)."""
    n: int                              # wave length (vids requested)
    parts: list                         # _WavePart covering positions 0..n-1
    stages: WaveStages = dataclasses.field(default_factory=WaveStages)
    _mats: Optional[list] = dataclasses.field(default=None, repr=False)

    @classmethod
    def from_mats(cls, mats: Sequence) -> "WaveResult":
        wr = cls(n=len(mats), parts=[])
        wr._mats = list(mats)
        return wr

    @property
    def delivered(self) -> bool:
        return self._mats is not None

    def ready(self) -> bool:
        """True when ``materialize()`` would not block on the device — the
        in-flight kernel(s) have finished (host-resident parts are always
        ready; a backend without ``is_ready`` conservatively reports
        True)."""
        if self._mats is not None:
            return True
        for p in self.parts:
            if p.mats is not None or p.packed is None:
                continue
            obj = p.packed
            if isinstance(obj, concurrent.futures.Future):
                if not obj.done():
                    return False
                if obj.exception() is not None:
                    continue        # ready to FAIL: materialize() raises it
                obj = obj.result()
            is_ready = getattr(obj, "is_ready", None)
            if is_ready is not None and not is_ready():
                return False
        return True

    def materialize(self) -> list:
        """Per-vid blocks in request order (device→host + split on first
        call, cached after)."""
        if self._mats is None:
            out: list = [None] * self.n
            for p in self.parts:
                for i, m in zip(p.idxs, p.split()):
                    out[i] = m
            self._mats = out
        return self._mats


# --------------------------------------------------------------- superblock --

@dataclasses.dataclass
class Superblock:
    """Every partition's block concatenated into one gatherable array.

    Layout: partition p owns rows [row_offsets[p], row_offsets[p] + R_p) of
    ``host``; each segment is padded to a BLOCK_N multiple (``bounds[p]`` is
    the aligned exclusive end — the safe upper limit for a run DMA landing
    in p), and D is padded to a multiple of 128 lanes.  ``device()`` uploads
    once and pins the copy in the kernels' lane-row layout — the row-major
    view ``(R_pad * row_lanes, 128)`` of ``host`` (``kernels.plan_launch``)
    — so the kernels consume it as-is; the epoch captured at build keys
    cache invalidation.

    A whole-store superblock covers every partition (``pids`` is None and
    segment i belongs to partition i); a PARTITION-GROUP superblock covers
    the subset ``pids`` — segment i belongs to partition ``pids[i]`` and
    ``slot`` maps a pid back to its segment.
    """
    host: np.ndarray          # (R_pad, D_pad) zero-padded concatenation
    row_offsets: np.ndarray   # (P,) int64 — first superblock row of segment p
    bounds: np.ndarray        # (P,) int64 — aligned exclusive end of segment p
    d: int                    # original feature width (pre-padding)
    block_n: int              # row alignment of the partition segments
    epoch: int                # store.epoch at build time
    _device: object = dataclasses.field(default=None, repr=False)
    uploads: int = 0          # host→device transfers performed
    bytes_uploaded: int = 0   # host→device bytes those transfers moved
    launches: int = 0         # kernel gathers run off this superblock
    cache_key: object = None  # the get_superblock args this is cached under
    pids: Optional[np.ndarray] = None   # group members (None = all partitions)
    _slot_of: Optional[dict] = dataclasses.field(default=None, repr=False)
    # wave-plan memo (see plan_wave_cached): keyed by the requested vid
    # tuple; safe because a superblock is immutable and epoch-bound — the
    # cache dies with it on eviction/migration
    _plan_cache: Optional["collections.OrderedDict"] = \
        dataclasses.field(default=None, repr=False)

    @property
    def n_rows(self) -> int:
        return self.host.shape[0]

    @property
    def row_lanes(self) -> int:
        """Lane-rows per superblock row in the device layout."""
        from ..kernels.plan_launch import LANES
        return self.host.shape[1] // LANES

    def slot(self, pid: int) -> int:
        """Segment index of partition ``pid`` in this superblock — the pid
        itself for a whole-store superblock, the group-local position for a
        partition-group one, -1 when the partition is not covered."""
        if self.pids is None:
            return pid if 0 <= pid < len(self.row_offsets) else -1
        if self._slot_of is None:
            self._slot_of = {int(p): i for i, p in enumerate(self.pids)}
        return self._slot_of.get(int(pid), -1)

    def device(self):
        """The device-resident lane-row copy — uploaded on first use, then
        pinned."""
        if self._device is None:
            fault_point("superblock.upload")
            import jax.numpy as jnp
            from ..kernels.plan_launch import LANES
            self._device = jnp.asarray(self.host.reshape(-1, LANES))
            self.uploads += 1
            self.bytes_uploaded += int(self.host.nbytes)
        return self._device


def _superblock_layout(parts, block_n: Optional[int]):
    """The (block_n, row_offsets, bounds, d, d_pad, total_rows, dtype) layout
    a superblock over ``parts`` would have — shared by ``build_superblock``,
    ``estimate_superblock_bytes`` and ``migrate_superblock`` so all three
    agree byte-for-byte.  D is padded to a multiple of 128 lanes: the
    device copy is the lane-row view of the host array."""
    from ..kernels.checkout_gather import DEFAULT_BN
    from ..kernels.plan_launch import LANES
    bn = DEFAULT_BN if block_n is None else block_n
    d = max((p.block.shape[1] for p in parts), default=0)
    d_pad = -(-max(d, 1) // LANES) * LANES
    seg = np.array([-(-p.block.shape[0] // bn) * bn for p in parts], np.int64)
    row_offsets = np.concatenate([[0], np.cumsum(seg)[:-1]]).astype(np.int64) \
        if len(parts) else np.zeros(0, np.int64)
    bounds = row_offsets + seg
    total = max(int(seg.sum()), bn)
    dtype = parts[0].block.dtype if parts else np.dtype(np.int32)
    return bn, row_offsets, bounds, d, d_pad, total, dtype


def _select_parts(store, pids):
    if pids is None:
        return store.partitions
    return [store.partitions[int(q)] for q in pids]


def estimate_superblock_bytes(store, *, block_n: Optional[int] = None,
                              pids: Optional[Sequence[int]] = None) -> int:
    """Host bytes a ``build_superblock`` call would allocate (the device
    copy pins the same amount), WITHOUT building it — the memory-budget
    check reads this before committing to the copy.  ``pids`` restricts the
    estimate to a partition group."""
    _, _, _, _, d_pad, total, dtype = _superblock_layout(
        _select_parts(store, pids), block_n)
    return total * d_pad * np.dtype(dtype).itemsize


def _cached_superblock_need(store) -> int:
    """``estimate_superblock_bytes`` under the DEFAULT tiling, memoized per
    epoch on the store — the over-budget wave path consults it on EVERY
    kernel wave and the value only changes on an epoch bump (O(P) python
    otherwise, paid on the latency-critical serve path)."""
    epoch = int(getattr(store, "epoch", 0))
    cached = getattr(store, "_superblock_need", None)
    if cached is not None and cached[0] == epoch:
        return cached[1]
    need = estimate_superblock_bytes(store)
    try:
        store._superblock_need = (epoch, need)
    except AttributeError:
        pass
    return need


def partition_segment_bytes(store, *,
                            block_n: Optional[int] = None) -> np.ndarray:
    """Per-partition BN-aligned segment bytes under the superblock layout —
    the additive unit the group former packs against the budget (a group's
    superblock is the concatenation of its members' segments)."""
    _, row_offsets, bounds, _, d_pad, _, dtype = _superblock_layout(
        store.partitions, block_n)
    return (bounds - row_offsets) * d_pad * np.dtype(dtype).itemsize


def build_superblock(store, *, block_n: Optional[int] = None,
                     pids: Optional[Sequence[int]] = None) -> Superblock:
    """Concatenate ``store.partitions`` blocks (padded to a common D) into
    one Superblock — all of them, or the partition group ``pids``."""
    parts = _select_parts(store, pids)
    bn, row_offsets, bounds, d, d_pad, total, dtype = _superblock_layout(
        parts, block_n)
    host = np.zeros((total, d_pad), dtype=dtype)
    for p, off in zip(parts, row_offsets):
        r, pd = p.block.shape
        host[off:off + r, :pd] = p.block
    return Superblock(host=host, row_offsets=row_offsets, bounds=bounds,
                      d=d, block_n=bn,
                      epoch=int(getattr(store, "epoch", 0)),
                      pids=None if pids is None
                      else np.asarray(list(pids), np.int64))


def get_superblock(store, *, block_n: Optional[int] = None,
                   max_bytes: Optional[int] = None
                   ) -> tuple[Optional[Superblock], bool]:
    """Epoch-keyed superblock cache, attached to the store.

    Returns (superblock, cache_hit).  A hit means the (host AND any pinned
    device) copy is reused verbatim — consecutive waves skip both the
    concatenation and the host→device transfer.  Bumping ``store.epoch``
    (partition rebuild) invalidates every cached shape.

    ``max_bytes`` is the memory budget: when no epoch-current copy is
    cached and the would-be superblock exceeds the budget, the call REFUSES
    to build one and returns (None, False) — callers route the wave through
    ``checkout_partitioned_perpart`` instead of OOMing.  The refusal is
    logged once per store.  An already-cached copy is returned regardless
    (its memory is already paid).
    """
    cache = getattr(store, "_superblock_cache", None)
    if cache is None:
        cache = {}
        try:
            store._superblock_cache = cache
        except AttributeError:          # store forbids attributes: no cache
            cache = None
    key = (block_n,)
    epoch = int(getattr(store, "epoch", 0))
    if cache is not None:
        sb = cache.get(key)
        if sb is not None and sb.epoch == epoch:
            return sb, True
    if max_bytes is not None:
        need = estimate_superblock_bytes(store, block_n=block_n)
        if need > max_bytes:
            _log_budget_refusal(store, need, max_bytes, epoch)
            return None, False
    sb = build_superblock(store, block_n=block_n)
    sb.cache_key = key
    if cache is not None:
        cache[key] = sb
    # the whole-store copy supersedes the partial-fusion layer: release any
    # pinned partition-group superblocks so the two never double-pin
    mgr = getattr(store, "_superblock_groups", None)
    if mgr is not None:
        mgr.evict_all()
    return sb, False


def _log_budget_refusal(store, need: int, max_bytes: int, epoch: int) -> None:
    """Log a whole-store superblock budget refusal ONCE per store — re-armed
    whenever the budget value or the epoch changes (a one-shot flag would go
    silent forever after the first refusal, hiding later layout/budget
    changes from the operator)."""
    state = (int(epoch), int(max_bytes))
    if getattr(store, "_superblock_budget_logged", None) == state:
        return
    try:
        store._superblock_budget_logged = state
    except AttributeError:
        pass
    logger.warning(
        "superblock needs %d bytes > max_bytes=%d: refusing to pin the "
        "whole store; waves route through partition-group superblocks "
        "(per-partition engine for unpinned stragglers)", need, max_bytes)


def evict_superblocks(store) -> int:
    """Eagerly drop EVERY cached superblock, pinned device copy included.

    ``repartition``/``apply_migration`` call this so a stale device buffer
    is released the moment the layout changes, instead of lingering until
    the next ``get_superblock`` happens to overwrite its cache slot (the
    old behavior leaked one device-resident ΣR×D copy per epoch bump).
    Any pinned partition-GROUP superblocks are dropped too (their eviction
    count accumulates on the group manager, not here) — the incremental
    path detaches them FIRST with ``take_group_superblocks`` and migrates
    them per group via ``migrate_groups``.
    Returns the eviction count; the all-time count accumulates on
    ``store._superblock_evictions``.
    """
    mgr = getattr(store, "_superblock_groups", None)
    if mgr is not None:
        mgr.evict_all()
    cache = getattr(store, "_superblock_cache", None)
    if not cache:
        return 0
    n = len(cache)
    for sb in cache.values():
        sb._device = None       # hard-release even if a caller kept a ref
    cache.clear()
    try:
        store._superblock_evictions = \
            getattr(store, "_superblock_evictions", 0) + n
    except AttributeError:
        pass
    return n


def take_superblock(store) -> Optional[Superblock]:
    """Remove and return an epoch-current cached superblock, device copy
    INTACT — migration consumes the old device buffer as its copy source
    even as the store stops pinning it.  Stale entries encountered on the
    way are evicted (counted); returns None when nothing current is
    cached."""
    cache = getattr(store, "_superblock_cache", None)
    if not cache:
        return None
    epoch = int(getattr(store, "epoch", 0))
    taken = None
    stale = 0
    for k in list(cache):
        if taken is None and cache[k].epoch == epoch:
            taken = cache.pop(k)
        elif cache[k].epoch != epoch:
            cache.pop(k)._device = None
            stale += 1
    if stale:
        try:
            store._superblock_evictions = \
                getattr(store, "_superblock_evictions", 0) + stale
        except AttributeError:
            pass
    return taken


def reinstall_superblock(store, sb: Optional[Superblock]) -> bool:
    """Rollback of ``take_superblock``: put a detached, still epoch-current
    superblock back into the store's cache (device copy intact).

    The trigger's migration path detaches the superblock BEFORE committing
    the migration; when the commit fails (an injected ``migration.commit``
    fault, an allocator error while staging), the store is still on the old
    layout and the detached copy is still valid — dropping it would leak
    the upload the next wave then pays again.  A stale (epoch-mismatched)
    superblock is released instead.  Returns True iff the copy was kept."""
    if sb is None:
        return False
    if sb.epoch != int(getattr(store, "epoch", 0)):
        sb._device = None
        return False
    cache = getattr(store, "_superblock_cache", None)
    if cache is None:
        cache = {}
        try:
            store._superblock_cache = cache
        except AttributeError:
            sb._device = None
            return False
    cache[sb.cache_key if sb.cache_key is not None else (None,)] = sb
    return True


def peek_superblock(store) -> Optional[Superblock]:
    """A cached, epoch-current superblock — or None, WITHOUT building one.
    The host gather path uses this so pure-host processes never pay the
    superblock's memory copy; only processes that run the kernel path (and
    therefore hold one anyway) get the fused host gather off it."""
    cache = getattr(store, "_superblock_cache", None)
    if not cache:
        return None
    epoch = int(getattr(store, "epoch", 0))
    for sb in cache.values():
        if sb.epoch == epoch:
            return sb
    return None


# ----------------------------------------------- partition-group superblocks --

GROUP_FANOUT = 4   # soft co-residency target: per-group cap = budget/FANOUT,
                   # so ~FANOUT hot groups can stay pinned simultaneously
                   # (a single partition bigger than the cap still gets its
                   # own group as long as it fits the whole budget)


@dataclasses.dataclass
class GroupWaveReport:
    """Accounting for ONE wave routed through the group layer."""
    groups_touched: int = 0    # distinct groups the wave's vids map to
    launches: int = 0          # fused kernel launches (== pinned groups that
                               # actually gathered tiles)
    pinned: int = 0            # groups (re)pinned by this wave
    evictions: int = 0         # LRU evictions this wave forced
    straggler_vids: int = 0    # vids routed through the per-partition engine


class SuperblockGroups:
    """Budget-aware partition-group superblock cache: the partial-fusion
    layer for stores whose whole-store superblock exceeds ``max_bytes``.

    The partition set is packed into groups, hot partitions first (the
    ``core.online.HotSetPolicy`` ranking when one is attached, partition
    order otherwise); each group's superblock is built and pinned ON DEMAND
    the first time a wave touches it, under the SHARED byte budget —
    pinning a new group LRU-evicts cold ones (never a group the current
    wave still needs).  Partitions bigger than the whole budget are
    permanent stragglers and always route through the per-partition
    engine.

    Invariants the leak tests hold us to: ``pinned_bytes`` equals the sum
    of the pinned groups' host bytes and never exceeds ``budget``;
    ``pins - evictions == len(groups)``; every superblock that leaves the
    cache has its device copy released (unless explicitly taken for
    migration, in which case ``migrate_groups`` releases it)."""

    def __init__(self, store, budget: int, *,
                 block_n: Optional[int] = None):
        self.store = store
        self.budget = int(budget)
        self.block_n = block_n
        self.epoch = int(getattr(store, "epoch", 0))
        # pinned group superblocks, LRU order (oldest first)
        self.groups: "collections.OrderedDict[tuple, Superblock]" = \
            collections.OrderedDict()
        self.pid_to_group: dict[int, tuple] = {}
        self.group_bytes: dict[tuple, int] = {}
        self.straggler_pids: set[int] = set()
        self.planned: list[tuple] = []      # group keys, hot order
        self.pinned_bytes = 0
        # all-time counters (the serve stats and the leak test read these)
        self.pins = 0
        self.evictions = 0
        self.launches = 0
        self.waves = 0
        self.groups_touched = 0
        self.straggler_requests = 0
        self.auto_regroups = 0      # heat-drift regroups maybe_regroup fired
        self.migrated = 0           # groups migrated on the device
                                    # (segment_move)
        self.extended = 0           # groups grown in place on the device by
                                    # ingest waves (segment_append)
        self.lazy_rebuilds = 0      # group migrations that fell back to a
                                    # rebuild on next touch
        self.append_evictions = 0   # ingest extensions that fell back to
                                    # eviction + rebuild on next touch
        self.last_wave: Optional[GroupWaveReport] = None
        self._plan_epoch = -1
        # heat-drift auto-regroup knobs (see maybe_regroup): every
        # ``auto_regroup_every`` group waves the CURRENT hot ranking is
        # compared against the prefix the plan was packed around; overlap
        # below 1 - ``drift_threshold`` triggers a clean regroup()
        self.auto_regroup_every = 32
        self.drift_threshold = 0.5
        self._plan_hot: list[int] = []

    # -- group formation ----------------------------------------------------
    def _hot_order(self, n_partitions: int) -> list[int]:
        pol = getattr(self.store, "_hot_set_policy", None)
        if pol is None:
            return list(range(n_partitions))
        return [int(q) for q in pol.rank(self.store, n_partitions)]

    def plan_groups(self) -> None:
        """(Re)partition the partition set into budget-fitting groups.

        Epoch-current PINNED groups keep their membership (their memory is
        already paid — regrouping must not thrash them); the remaining
        partitions are packed greedily in hot order against the per-group
        cap.  A partition bigger than the whole budget becomes a straggler
        (permanently perpart-routed)."""
        store = self.store
        self.epoch = int(getattr(store, "epoch", 0))
        seg = partition_segment_bytes(store, block_n=self.block_n)
        n = len(seg)
        self.pid_to_group.clear()
        self.straggler_pids.clear()
        self.group_bytes.clear()
        self.planned = []
        for key in list(self.groups):
            sb = self.groups[key]
            if sb.epoch != self.epoch or any(q >= n for q in key):
                self._evict(key)
                continue
            self.group_bytes[key] = int(sb.host.nbytes)
            self.planned.append(key)
            for q in key:
                self.pid_to_group[q] = key
        cap = max(self.budget // GROUP_FANOUT, 1)
        cur: list[int] = []
        cur_bytes = 0

        def close() -> None:
            nonlocal cur, cur_bytes
            if cur:
                key = tuple(sorted(cur))
                self.group_bytes[key] = estimate_superblock_bytes(
                    self.store, block_n=self.block_n, pids=key)
                self.planned.append(key)
                for q in cur:
                    self.pid_to_group[q] = key
            cur, cur_bytes = [], 0

        for q in self._hot_order(n):
            if q in self.pid_to_group:
                continue                    # already kept via a pinned group
            b = int(seg[q])
            if b > self.budget:
                self.straggler_pids.add(q)
                continue
            if cur and cur_bytes + b > cap:
                close()
            cur.append(q)
            cur_bytes += b
        close()
        # remember the hot prefix this plan packed its co-resident groups
        # around — maybe_regroup measures drift as loss of overlap between
        # it and the LIVE ranking (~GROUP_FANOUT groups fit the budget, so
        # that's the set whose staleness costs launches)
        n_hot = sum(len(k) for k in self.planned[:GROUP_FANOUT])
        self._plan_hot = [q for q in self._hot_order(n)
                          if q not in self.straggler_pids][:n_hot]
        self._plan_epoch = self.epoch

    def ensure_plan(self) -> None:
        if (self._plan_epoch != int(getattr(self.store, "epoch", 0))
                or (not self.pid_to_group and not self.straggler_pids
                    and len(self.store.partitions))):
            self.plan_groups()

    def set_budget(self, budget: int) -> None:
        """Budget changes re-form the groups from scratch (the cap moved);
        counters survive."""
        budget = int(budget)
        if budget == self.budget:
            return
        self.budget = budget
        self.evict_all()
        self._plan_epoch = -1

    def regroup(self) -> None:
        """Drop every pin and re-form the groups from the CURRENT hot
        ranking — the explicit consolidation knob for traffic shifts.
        The implicit replans (epoch bump, budget change) KEEP pinned
        groups, so heat that accumulated after the first plan can leave
        hot partitions scattered across cold-order groups; this one
        starts clean, so the hot set packs into dense co-resident groups
        (fewer launches per wave).  Costs a full re-pin on the next
        waves.  The RESULT (not the heat trigger) is journaled as an
        advisory record when a ``core.journal`` journal is attached, so a
        restored store replays the layout directly — heat EWMAs between
        snapshots are not journaled per wave."""
        self.evict_all()
        self._plan_epoch = -1
        self.ensure_plan()
        from .journal import journal_regroup     # lazy: no import cycle
        journal_regroup(self)

    def regroup_drift(self) -> float:
        """How far the LIVE hot ranking has drifted from the prefix the
        current plan packed around, in [0, 1]: 0 = the grouping still
        serves the hot set, 1 = the hot set moved entirely onto
        partitions the plan left in cold-order groups."""
        if not self._plan_hot:
            return 0.0
        if getattr(self.store, "_hot_set_policy", None) is None:
            return 0.0
        live = [q for q in self._hot_order(len(self.store.partitions))
                if q not in self.straggler_pids][:len(self._plan_hot)]
        if not live:
            return 0.0
        return 1.0 - len(set(live) & set(self._plan_hot)) / len(live)

    def maybe_regroup(self) -> bool:
        """Heat-driven automatic ``regroup()``: fires when the served hot
        set has drifted past ``drift_threshold`` from the current
        grouping, so one tenant's shifted traffic cannot permanently pin
        another tenant's now-cold groups out of budget.  ``_grouped_wave``
        calls this every ``auto_regroup_every`` group waves; routing-only,
        results are grouping-invariant.  Returns whether it fired."""
        drift = self.regroup_drift()
        if drift < self.drift_threshold:
            return False
        self.auto_regroups += 1
        logger.info("hot-set drift %.2f >= %.2f: auto regroup #%d",
                    drift, self.drift_threshold, self.auto_regroups)
        self.regroup()
        return True

    # -- pin / evict ---------------------------------------------------------
    def _evict(self, key: tuple) -> None:
        # fires BEFORE the pop: an injected eviction failure leaves the
        # victim pinned AND accounted (pins - evictions == len(groups))
        fault_point("group.evict", self.store)
        sb = self.groups.pop(key)
        sb._device = None                   # hard-release the device copy
        self.pinned_bytes -= int(sb.host.nbytes)
        self.evictions += 1

    def evict_all(self) -> int:
        n = len(self.groups)
        for key in list(self.groups):
            self._evict(key)
        return n

    def take_all(self) -> list[Superblock]:
        """Detach every pinned group, device copies INTACT — migration
        consumes them as copy sources.  Counted as evictions (the cache no
        longer owns the memory); ``migrate_groups`` releases the old
        buffers once the per-group migration has replayed them."""
        out = []
        for key in list(self.groups):
            sb = self.groups.pop(key)
            self.pinned_bytes -= int(sb.host.nbytes)
            self.evictions += 1
            out.append(sb)
        return out

    def _make_room(self, need: int, protected: frozenset | set) -> bool:
        """LRU-evict cold (non-``protected``) groups until ``need`` bytes
        fit under the budget; False when they cannot (oversize ``need`` or
        only protected groups left to evict)."""
        if need > self.budget:
            return False
        while self.pinned_bytes + need > self.budget:
            victim = next((k for k in self.groups if k not in protected),
                          None)
            if victim is None:
                return False
            self._evict(victim)
        return True

    def peek(self, key: tuple) -> Optional[Superblock]:
        """An already-pinned, epoch-current group superblock — or None,
        WITHOUT building one (the host tier's free-fusion check)."""
        sb = self.groups.get(key)
        if sb is None or sb.epoch != int(getattr(self.store, "epoch", 0)):
            return None
        self.groups.move_to_end(key)
        return sb

    def pin(self, key: tuple, protected: frozenset | set = frozenset()
            ) -> Optional[Superblock]:
        """The group's superblock, pinned — building it (and LRU-evicting
        cold groups to make room) if needed.  ``protected`` groups (the
        current wave's) are never evicted; returns None when the group
        cannot fit without evicting one of them."""
        sb = self.peek(key)
        if sb is not None:
            return sb
        # fires before any build/evict work: an injected pin failure pins no
        # bytes and leaves the LRU state untouched
        fault_point("group.pin", self.store)
        if key in self.groups:              # stale epoch: rebuild below
            self._evict(key)
        need = self.group_bytes.get(key)
        if need is None:
            need = estimate_superblock_bytes(
                self.store, block_n=self.block_n, pids=key)
            self.group_bytes[key] = need
        if not self._make_room(need, protected):
            return None
        sb = build_superblock(self.store, block_n=self.block_n, pids=key)
        sb.cache_key = key
        self.groups[key] = sb
        self.pinned_bytes += int(sb.host.nbytes)
        self.pins += 1
        return sb

    def install(self, sb: Superblock,
                protected: frozenset | set = frozenset()) -> bool:
        """Pin an externally built (migrated) group superblock under the
        budget, LRU-evicting cold groups to fit; on False the superblock's
        device copy is released (it could not be kept)."""
        key = tuple(int(q) for q in np.asarray(sb.pids))
        need = int(sb.host.nbytes)
        if not self._make_room(need, protected):
            sb._device = None
            return False
        sb.cache_key = key
        self.groups[key] = sb
        self.group_bytes[key] = need
        for q in key:
            self.pid_to_group[q] = key
        self.pinned_bytes += need
        self.pins += 1
        return True

    def warm(self, *, device: bool) -> int:
        """Pin planned groups, hot order first, until the budget is full —
        the serve-layer warmup analogue of ``Superblock.device()``.  A
        group that cannot fit is SKIPPED (not a stop): smaller, colder
        groups further down the plan may still fill the remaining
        budget."""
        self.ensure_plan()
        n = 0
        for key in list(self.planned):
            sb = self.pin(key, protected=set(self.groups))
            if sb is None:
                continue
            if device:
                sb.device()
            n += 1
        return n


def get_superblock_groups(store, *, budget: Optional[int] = None,
                          create: bool = False
                          ) -> Optional[SuperblockGroups]:
    """The store's group-superblock manager (attached like the superblock
    cache; None when absent and ``create`` is False or the store forbids
    attributes).  A ``budget`` differing from the manager's re-forms the
    groups; creation also attaches a ``core.online.HotSetPolicy`` so the
    group former has a hot ranking to consume."""
    mgr = getattr(store, "_superblock_groups", None)
    if mgr is None and create:
        if budget is None:
            raise ValueError("creating SuperblockGroups needs a budget")
        mgr = SuperblockGroups(store, budget)
        try:
            store._superblock_groups = mgr
        except AttributeError:
            return None
        from .online import get_hot_set_policy   # lazy: no cycle at import
        get_hot_set_policy(store, create=True)
    elif mgr is not None and budget is not None:
        mgr.set_budget(int(budget))
    return mgr


def take_group_superblocks(store) -> list[Superblock]:
    """Detach every pinned group superblock (device copies intact) ahead of
    a migration — ``migrate_groups`` replays them under the new layout."""
    mgr = getattr(store, "_superblock_groups", None)
    return mgr.take_all() if mgr is not None else []


def migrate_groups(store, plan, taken: Sequence[Superblock], *,
                   use_kernel: Optional[bool] = None) -> int:
    """Per-group epoch-bump migration: re-pin each detached pre-migration
    group superblock under the NEW layout instead of nuking the cache.

    Each old group's partitions map through ``plan.matched_old`` to the new
    partitions that morphed out of them; the group superblock migrates
    incrementally (``migrate_superblock(pids=...)`` — device tiles reused,
    delta-only upload) and re-pins under the budget.  Groups that dissolved
    (no new partition morphed from them), changed width, or no longer fit
    are evicted (device released).  Returns the migrated-group count."""
    mgr = getattr(store, "_superblock_groups", None)
    if mgr is None:
        for sb in taken:
            sb._device = None
        return 0
    matched = np.asarray(plan.matched_old, np.int64)
    migrated = 0
    kept: set[tuple] = set()    # groups migrated THIS call are protected:
    # installing a later group must not LRU-evict an earlier one whose
    # segment_move work was just paid (hot-order taken first)
    # Runs POST-COMMIT (store already on the new layout), so a failure here
    # must degrade, never propagate: each group falls back independently to
    # lazy rebuild, and the finally guarantees zero leaked device buffers.
    try:
        for old_sb in taken:
            old_pids = set(
                int(q) for q in (old_sb.pids if old_sb.pids is not None
                                 else np.arange(len(old_sb.row_offsets))))
            new_pids = sorted(int(i) for i in np.flatnonzero(matched >= 0)
                              if int(matched[i]) in old_pids)
            if not new_pids:
                old_sb._device = None
                continue
            # don't pay segment_move for a group that cannot be kept: every
            # group pinned during this call is protected, so the fit test is
            # exactly "does it fit in the remaining budget"
            est = estimate_superblock_bytes(store, block_n=mgr.block_n,
                                            pids=new_pids)
            if mgr.pinned_bytes + est > mgr.budget:
                old_sb._device = None
                continue
            try:
                new_sb, st = migrate_superblock(store, old_sb, plan,
                                                pids=new_pids,
                                                use_kernel=use_kernel,
                                                install=False)
            except ValueError:      # width changed: rebuild on next touch
                old_sb._device = None
                mgr.lazy_rebuilds += 1
                continue
            except Exception:       # transient (injected/allocator): this
                old_sb._device = None   # group rebuilds lazily, rest proceed
                mgr.lazy_rebuilds += 1
                logger.warning("group migration failed; falling back to "
                               "lazy rebuild", exc_info=True)
                continue
            old_sb._device = None
            if mgr.install(new_sb, protected=kept):
                kept.add(tuple(int(q) for q in np.asarray(new_sb.pids)))
                migrated += 1
                mgr.migrated += int(st.used_device)
        try:
            mgr.plan_groups()       # regroup leftovers around the survivors
        except Exception:
            mgr._plan_epoch = -1    # replan on next pin()
            logger.warning("post-migration regroup failed; deferring to "
                           "next pin", exc_info=True)
    finally:
        for old_sb in taken:        # no device buffer outlives this call
            old_sb._device = None
    return migrated


# ---------------------------------------------------------------- wave plan --

@dataclasses.dataclass(frozen=True)
class WavePlan:
    """A cross-partition gather plan: one flat tile plan over the superblock.

    ``plan`` is the adaptive (starts, mode) plan from ``plan_batched`` over
    the REBASED rlists (local rid + partition row offset); ``hi`` carries the
    per-tile exclusive row bound the kernel checks before a run DMA.
    """
    plan: object              # kernels.checkout_batched.BatchedPlan
    hi: np.ndarray            # (T,) int32 per-tile run-DMA bound
    rebased: list             # the rebased rlists (host-path gather input)

    @property
    def n_tiles(self) -> int:
        return self.plan.n_tiles

    def segment(self, k: int, block_n: int) -> slice:
        return self.plan.segment(k, block_n)


def _rebase_wave(store, vids: Sequence[int], sb: Superblock
                 ) -> tuple[list[np.ndarray], list[int]]:
    """Rebase each version's LOCAL rlist into superblock coordinates (local
    rid + the partition SEGMENT's row offset — segment == pid for a
    whole-store superblock, the group slot for a partition-group one).
    The host path gathers straight off this; the kernel path plans it with
    ``plan_wave``.  Returns (rebased rlists, per-vid segment slots)."""
    rebased: list[np.ndarray] = []
    slots: list[int] = []
    for v in vids:
        pid = int(store.vid_to_pid[int(v)])
        s = sb.slot(pid)
        if s < 0:
            raise ValueError(
                f"version {int(v)}'s partition {pid} is not covered by "
                f"this superblock (group {None if sb.pids is None else list(sb.pids)})")
        p = store.partitions[pid]
        rebased.append(np.asarray(p.local_rlist(int(v)), np.int64)
                       + int(sb.row_offsets[s]))
        slots.append(s)
    return rebased, slots


def plan_wave(store, vids: Sequence[int], sb: Superblock, *,
              density_threshold: float = 0.05) -> WavePlan:
    """Plan a multi-partition wave as ONE flat tile plan.

    Each version's local rlist is rebased by its partition's superblock row
    offset, then the whole wave is planned back to back by ``plan_batched``
    exactly as if it came from a single block.  Two wave-only extensions:

      * ``hi[t]`` = the aligned end of tile t's partition segment — the run
        bound the kernel verifies on device;
      * consecutive TAIL chunks are promoted to run DMAs (mode 1): the
        padding rows a full (BN, BD) read drags in stay inside the
        partition's aligned segment and land in the sliced-off region of the
        output, so the promotion turns BN row DMAs into ONE run DMA for
        every dense version whose length isn't a BN multiple.
    """
    from ..kernels.checkout_batched import plan_batched
    bn = sb.block_n
    rebased, slots = _rebase_wave(store, vids, sb)
    plan = plan_batched(rebased, block_n=bn,
                        density_threshold=density_threshold)
    # vectorized like plan_batched itself (this runs on the serve host
    # thread under the previous wave's in-flight kernel): per-tile bounds
    # by one repeat, tail promotion read off the flat padded plan
    t_per = np.diff(plan.tile_offsets)
    hi = np.repeat(np.asarray(sb.bounds)[np.asarray(slots, np.int64)],
                   t_per).astype(np.int32)
    mode = plan.mode.copy()
    if bn > 1 and plan.n_tiles:
        nz = np.flatnonzero(t_per)
        # tail promotion: a ragged final chunk whose VALID rids are
        # consecutive goes out as one run DMA (padding repeats the last
        # rid, so only the first tail_len-1 plan diffs must equal 1)
        last_idx = (plan.tile_offsets[1:] - 1)[nz]
        tail_len = plan.n_rows[nz] - (t_per[nz] - 1) * bn
        cand = tail_len < bn
        if cand.any():
            chunks = plan.starts.reshape(-1, bn)[last_idx[cand]] \
                .astype(np.int64)
            consec = np.cumprod(np.diff(chunks, axis=1) == 1, axis=1)
            tl = tail_len[cand]
            ok = (tl <= 1) | consec[np.arange(len(tl)),
                                    np.maximum(tl - 2, 0)].astype(bool)
            mode[last_idx[cand][ok]] = 1
    plan = dataclasses.replace(plan, mode=mode)
    return WavePlan(plan=plan, hi=hi, rebased=rebased)


PLAN_CACHE_MAX = 64     # memoized wave plans kept per superblock (LRU)


def plan_wave_cached(store, vids: Sequence[int], sb: Superblock, *,
                     density_threshold: float = 0.05) -> WavePlan:
    """``plan_wave`` memoized on the superblock, keyed by the requested vid
    tuple.

    Steady serve traffic repeats hot wave shapes; replanning an identical
    wave is pure host overhead — and on the pipelined serve path it runs
    UNDER the previous wave's in-flight kernel, where it costs twice.  The
    memo is correct by construction: a plan is a deterministic function of
    (layout, vids, tiling), the layout only changes with the epoch, and the
    epoch-bound superblock carrying the cache is evicted on every epoch
    bump.  LRU-bounded at ``PLAN_CACHE_MAX`` entries."""
    key = (tuple(int(v) for v in vids), density_threshold)
    cache = sb._plan_cache
    if cache is None:
        cache = sb._plan_cache = collections.OrderedDict()
    wp = cache.get(key)
    if wp is not None:
        cache.move_to_end(key)
        return wp
    wp = plan_wave(store, vids, sb, density_threshold=density_threshold)
    cache[key] = wp
    while len(cache) > PLAN_CACHE_MAX:
        cache.popitem(last=False)
    return wp


def _validate_vids(store, vids: Sequence[int]) -> list[int]:
    if not isinstance(vids, (np.ndarray, list, tuple)):
        vids = list(vids)           # generators/iterators were always valid
    arr = np.asarray(vids, dtype=np.int64)
    if arr.ndim != 1:
        # the pre-vectorization int(v)-per-element loop raised on nested
        # input; silently flattening would serve a malformed request
        raise TypeError(
            f"vids must be a flat sequence of ints, got shape {arr.shape}")
    n_versions = len(store.vid_to_pid)
    oob = (arr < 0) | (arr >= n_versions)
    if oob.any():
        bad = [int(v) for v in arr[oob]]
        raise ValueError(f"unknown version id(s) {bad}: store has "
                         f"{n_versions} versions (0..{n_versions - 1})")
    return arr.tolist()


def _perpart_fallback(store, vids: Sequence[int],
                      stats: Optional[DensityStats], use_kernel,
                      density_threshold: float) -> list[np.ndarray]:
    """Route a whole wave through the per-partition engine, recording the
    wave's density telemetry off the local rlists first (rebasing is a
    constant per-version offset, so local density == superblock density) —
    the shared tail of every wave-engine fallback branch."""
    if stats:
        stats.record(vids, *_local_wave_density(store, vids,
                                                density_threshold))
    return checkout_partitioned_perpart(store, vids, use_kernel=use_kernel)


def _local_wave_density(store, vids: Sequence[int],
                        density_threshold: float):
    """(density, tiles) off the versions' LOCAL rlists — the telemetry for
    waves that bypass the superblock (rebasing adds a constant per-version
    offset, so local and rebased densities are identical).  Imports lazily:
    only monitored stores pay the kernels (jax) import on the host path."""
    from ..kernels.checkout_gather import DEFAULT_BN
    rls = [store.partitions[int(store.vid_to_pid[int(v)])].local_rlist(int(v))
           for v in vids]
    return measure_density(rls, DEFAULT_BN,
                           density_threshold=density_threshold)


def checkout_wave(store, vids: Sequence[int], *,
                  use_kernel: Optional[bool] = None,
                  density_threshold: float = 0.05,
                  max_bytes: Optional[int] = None,
                  record_density: bool = True,
                  device_out: bool = False):
    """Cross-partition fused checkout: the whole wave, ONE kernel launch.

    However many partitions the vids span, the wave executes as a single
    ``checkout_wave`` pallas_call over the store's cached device-resident
    superblock.  The superblock (a padded copy of EVERY partition block) is
    only built when the fusion can pay for it: waves confined to one
    partition with no superblock cached already run as one launch through
    the per-partition engine, the host path gathers off a superblock only
    when one is already cached (free fusion), and a store whose superblock
    would exceed ``max_bytes`` (default: ``store.superblock_max_bytes``)
    refuses the whole-store copy and routes through the PARTITION-GROUP
    layer instead — one fused launch per touched pinned group
    (``SuperblockGroups``), the per-partition engine only for genuinely
    unpinned stragglers.

    Every planned wave also records per-vid run-density telemetry into the
    store's ``DensityStats`` — ONCE an accumulator is attached
    (``core.online.RepartitionTrigger`` attaches one; so does
    ``get_density_stats(store, create=True)``).  Stores nobody monitors pay
    nothing.  ``record_density=False`` opts a call out entirely.  An
    attached ``HotSetPolicy`` likewise observes every wave's touched
    partitions (the group former's heat signal).

    ``device_out=True`` returns a ``WaveResult`` handle instead of host
    blocks: kernel-tier gathers stay DEVICE-resident and in flight (the
    launch returns without blocking — natively via JAX async dispatch, and
    through the ``_wave_launcher`` worker on backends whose dispatch
    executes inline), host/perpart tiers come back pre-materialized behind
    the same handle — ``materialize()`` later is bit-identical to the
    eager path."""
    res = _wave_result(store, vids, use_kernel=use_kernel,
                       density_threshold=density_threshold,
                       max_bytes=max_bytes, record_density=record_density,
                       defer=device_out)
    return res if device_out else res.materialize()


def _wave_result(store, vids: Sequence[int], *,
                 use_kernel: Optional[bool],
                 density_threshold: float,
                 max_bytes: Optional[int],
                 record_density: bool,
                 defer: bool = False) -> WaveResult:
    """``checkout_wave``'s body: route the wave, return a WaveResult."""
    vids = _validate_vids(store, vids)
    if not vids:
        return WaveResult.from_mats([])
    if use_kernel is None:
        use_kernel = _default_use_kernel()
    if max_bytes is None:
        max_bytes = getattr(store, "superblock_max_bytes", None)
    stats = get_density_stats(store) if record_density else None
    pol = getattr(store, "_hot_set_policy", None)
    if pol is not None:
        pol.touch([int(store.vid_to_pid[int(v)]) for v in vids])
    sb = peek_superblock(store)
    if not use_kernel:
        # Host tier: reuse an ALREADY-CACHED superblock for the one-take
        # fused gather, but never build one just for numpy — np.take off the
        # per-partition blocks is parity-fast and costs no extra copy.
        if sb is None:
            mgr = getattr(store, "_superblock_groups", None)
            if mgr is not None and mgr.groups:
                # free fusion off already-pinned group superblocks
                return _grouped_wave(store, vids, mgr, use_kernel=False,
                                     stats=stats,
                                     density_threshold=density_threshold)
            return WaveResult.from_mats(_perpart_fallback(
                store, vids, stats, False, density_threshold))
        rebased, _ = _rebase_wave(store, vids, sb)
        if stats:
            stats.record(vids, *measure_density(
                rebased, sb.block_n, density_threshold=density_threshold))
        return WaveResult.from_mats(
            _fused_host_gather(sb.host[:, :sb.d], rebased))
    if sb is None and max_bytes is not None:
        need = _cached_superblock_need(store)
        if need > max_bytes:
            # over budget: refuse the whole-store copy, run the wave through
            # the partition-group layer (partial fusion under the budget)
            _log_budget_refusal(store, need, max_bytes,
                                int(getattr(store, "epoch", 0)))
            store_budget = getattr(store, "superblock_max_bytes", None)
            mgr = get_superblock_groups(store)
            if mgr is None:
                # the SHARED manager is sized by the store-level budget; a
                # per-call max_bytes only seeds it when no store-level
                # budget exists at all
                mgr = get_superblock_groups(
                    store, create=True,
                    budget=store_budget if store_budget is not None
                    else max_bytes)
            elif max_bytes == store_budget:
                # a store-level budget change re-forms the shared manager;
                # a per-call max_bytes override only bounds THIS wave's
                # whole-store build decision — mutating the shared budget
                # would evict every other caller's pinned groups
                mgr.set_budget(max_bytes)
            if mgr is not None:
                return _grouped_wave(store, vids, mgr, use_kernel=True,
                                     stats=stats,
                                     density_threshold=density_threshold,
                                     defer=defer)
            # store forbids attributes: no group cache possible
            return WaveResult.from_mats(_perpart_fallback(
                store, vids, stats, use_kernel, density_threshold))
    if sb is None and len({int(store.vid_to_pid[v]) for v in vids}) <= 1:
        # one partition touched = the per-partition engine is already a
        # single launch; don't build+pin a whole-store superblock for it
        return WaveResult.from_mats(_perpart_fallback(
            store, vids, stats, use_kernel, density_threshold))
    stages = WaveStages()
    if sb is None:
        t0 = time.perf_counter()
        with obs.span("checkout.pin"):
            sb, _ = get_superblock(store, max_bytes=max_bytes)
        stages.pin_s += time.perf_counter() - t0
        if sb is None:          # refused (store forbade caching): perpart
            return WaveResult.from_mats(_perpart_fallback(
                store, vids, stats, use_kernel, density_threshold))
    part, _, dt = _gather_off_superblock(
        store, vids, sb, stages, use_kernel=True,
        density_threshold=density_threshold, want_density=stats is not None,
        defer=defer)
    if stats:
        stats.record(vids, *dt)
    return WaveResult(n=len(vids), parts=[part], stages=stages)


def _gather_off_superblock(store, gvids: Sequence[int], sb: Superblock,
                           stages: WaveStages, *,
                           use_kernel: bool, density_threshold: float,
                           want_density: bool = False, defer: bool = False
                           ) -> tuple[_WavePart, bool, Optional[tuple]]:
    """One fused gather for ``gvids`` over ``sb`` (whole-store or group).
    Returns (part, launched, density) — ``part`` is a ``_WavePart`` over
    positions 0..len(gvids)-1 (kernel tier: the DEVICE-resident packed
    gather + split plan, the device→host transfer deferred to ``split()``;
    host tier: pre-materialized blocks); ``launched`` is True iff a kernel
    launch actually happened (an all-empty wave gathers nothing);
    ``density`` is the per-vid (densities, tiles) telemetry when
    ``want_density`` (read off the plan the gather needs anyway — no extra
    rlist pass), else None.  ``defer=True`` launches the jitted gather on
    the ``_wave_launcher`` worker so the call returns with the kernel in
    flight even on inline-dispatch backends; planning and the ``device()``
    pin stay on this thread.  The kernel tier adds its plan, first-upload
    and launch seconds, the upload's bytes, and its planned and pad tiles
    to ``stages``."""
    idxs = list(range(len(gvids)))
    if not use_kernel:
        rebased, _ = _rebase_wave(store, gvids, sb)
        dt = measure_density(rebased, sb.block_n,
                             density_threshold=density_threshold) \
            if want_density else None
        return _WavePart(idxs=idxs, mats=_fused_host_gather(
            sb.host[:, :sb.d], rebased)), False, dt
    t0 = time.perf_counter()
    with obs.span("checkout.plan", vids=len(gvids)):
        wp = plan_wave_cached(store, gvids, sb,
                              density_threshold=density_threshold)
    plan_s = time.perf_counter() - t0
    dt = _plan_mode_density(wp.plan) if want_density else None
    if wp.n_tiles == 0:
        stages.plan_s += plan_s
        empty = np.zeros((0, sb.d), dtype=sb.host.dtype)
        return _WavePart(idxs=idxs, mats=[empty for _ in gvids]), False, dt
    from ..kernels import ops as K
    pin_s, up = 0.0, 0
    if sb._device is None:      # upload/pin on the CALLER's thread
        t0, up = time.perf_counter(), sb.bytes_uploaded
        with obs.span("checkout.pin", bytes=int(sb.host.nbytes)):
            sb.device()
        pin_s, up = time.perf_counter() - t0, sb.bytes_uploaded - up
    dev = sb.device()
    # fires after planning + upload, before the pallas_call: a retry finds
    # the plan memo and the pinned device copy intact and just relaunches
    fault_point("wave.launch", store)
    t0 = time.perf_counter()
    with obs.span("checkout.launch", tiles=wp.n_tiles):
        if defer and _defer_via_worker(wp.n_tiles):
            packed = _wave_launcher().submit(
                K.checkout_wave, dev, wp.plan.starts, wp.plan.mode, wp.hi,
                block_n=sb.block_n, row_lanes=sb.row_lanes)
        else:
            packed = K.checkout_wave(dev, wp.plan.starts, wp.plan.mode,
                                     wp.hi, block_n=sb.block_n,
                                     row_lanes=sb.row_lanes)
    stages.launch_s += time.perf_counter() - t0
    stages.tiles += wp.n_tiles
    stages.pad_tiles += K.launch_tiles(wp.n_tiles) - wp.n_tiles
    stages.plan_s += plan_s
    stages.pin_s += pin_s
    stages.h2d_bytes += up
    sb.launches += 1
    return _WavePart(idxs=idxs, packed=packed,
                     segments=[wp.segment(k, sb.block_n)
                               for k in range(len(gvids))],
                     d=sb.d, width=sb.host.shape[1], stages=stages), True, dt


def _grouped_wave(store, vids: Sequence[int], mgr: SuperblockGroups, *,
                  use_kernel: bool, stats: Optional[DensityStats],
                  density_threshold: float, defer: bool = False
                  ) -> WaveResult:
    """Route one wave through the partition-group layer.

    The wave's vids split by group; every touched group that is (or can
    be) pinned runs as ONE fused ``checkout_wave`` gather over its
    group superblock — kernel launches == touched pinned groups, and every
    launched gather stays device-resident inside the returned
    ``WaveResult`` (the per-group device→host transfers all defer to
    ``materialize()``).  Groups this wave touches are protected from
    intra-wave LRU eviction (pinning group B must not thrash group A
    mid-wave); vids whose group cannot co-pin, plus straggler partitions
    bigger than the whole budget, route through the per-partition engine
    in one batch.  The host tier only uses groups that are ALREADY pinned
    (free fusion — numpy never pays a superblock build)."""
    # heat-driven auto-regroup checkpoint: every auto_regroup_every group
    # waves, re-form the groups when the live hot ranking drifted from the
    # plan-time prefix (maybe_regroup) — a shifted hot set must not stay
    # scattered across a stale grouping
    if (mgr.auto_regroup_every and mgr.waves
            and mgr.waves % mgr.auto_regroup_every == 0):
        mgr.maybe_regroup()
    mgr.ensure_plan()
    by_group: dict[tuple, list[int]] = {}
    stragglers: list[int] = []
    for i, v in enumerate(vids):
        key = mgr.pid_to_group.get(int(store.vid_to_pid[int(v)]))
        if key is None:
            stragglers.append(i)
        else:
            by_group.setdefault(key, []).append(i)
    # density telemetry rides the per-group plans the gathers need anyway;
    # only straggler vids pay a separate local-rlist measurement
    dens = np.ones(len(vids), np.float64) if stats else None
    tiles = np.zeros(len(vids), np.int64) if stats else None
    report = GroupWaveReport(groups_touched=len(by_group))
    pins0, ev0 = mgr.pins, mgr.evictions
    protected = set(by_group)
    parts: list[_WavePart] = []
    stages = WaveStages()
    for key, idxs in by_group.items():
        sb = mgr.peek(key)
        if sb is None and use_kernel:
            t0 = time.perf_counter()
            with obs.span("checkout.pin", partitions=len(key)):
                sb = mgr.pin(key, protected=protected)
            stages.pin_s += time.perf_counter() - t0
        if sb is None:
            stragglers.extend(idxs)
            continue
        gvids = [vids[i] for i in idxs]
        part, launched, dt = _gather_off_superblock(
            store, gvids, sb, stages, use_kernel=use_kernel,
            density_threshold=density_threshold,
            want_density=stats is not None, defer=defer)
        if launched:
            report.launches += 1
            mgr.launches += 1
        parts.append(dataclasses.replace(part, idxs=idxs))
        if dt is not None:
            d_g, t_g = dt
            for j, i in enumerate(idxs):
                dens[i], tiles[i] = d_g[j], t_g[j]
    if stragglers:
        stragglers.sort()
        svids = [vids[i] for i in stragglers]
        t0 = time.perf_counter()
        with obs.span("checkout.stragglers", vids=len(svids)):
            mats = checkout_partitioned_perpart(store, svids,
                                                use_kernel=use_kernel,
                                                stages=stages)
        stages.straggler_s += time.perf_counter() - t0
        parts.append(_WavePart(idxs=list(stragglers), mats=list(mats)))
        if stats:
            d_s, t_s = _local_wave_density(store, svids, density_threshold)
            for j, i in enumerate(stragglers):
                dens[i], tiles[i] = d_s[j], t_s[j]
    if stats:
        stats.record(vids, dens, tiles)
    report.pinned = mgr.pins - pins0
    report.evictions = mgr.evictions - ev0
    report.straggler_vids = len(stragglers)
    mgr.waves += 1
    mgr.groups_touched += report.groups_touched
    mgr.straggler_requests += len(stragglers)
    mgr.last_wave = report
    return WaveResult(n=len(vids), parts=parts, stages=stages)


# ---------------------------------------------------- superblock migration --

@dataclasses.dataclass
class MigrationStats:
    """Accounting for one ``migrate_superblock`` call."""
    n_tiles: int                  # BN-row tiles in the NEW superblock
    reused_tiles: int             # device-to-device copies from the OLD one
    delta_tiles: int              # tiles shipped over the host link
    bytes_uploaded: int           # host->device bytes actually transferred
    bytes_total: int              # what a rebuild-from-scratch would upload
    used_device: bool             # device path taken (old device copy live)
    wall_s: float

    @property
    def reuse_fraction(self) -> float:
        return self.reused_tiles / self.n_tiles if self.n_tiles else 1.0


def migrate_superblock(store, old_sb: Superblock, plan, *,
                       use_kernel: Optional[bool] = None,
                       install: bool = True,
                       pids: Optional[Sequence[int]] = None
                       ) -> tuple[Superblock, MigrationStats]:
    """Incremental superblock migration: reuse the OLD device buffer.

    Called AFTER ``store.apply_migration(plan)`` with the PRE-migration
    superblock (grab it with ``take_superblock`` before applying).  Builds
    the post-migration superblock without the naive rebuild's full
    host→device re-upload.  ``pids`` migrates a partition GROUP instead of
    the whole store: the new superblock covers exactly those (new)
    partitions, and rows whose source partition lies outside the old group
    superblock ride the delta (``install`` is ignored for groups — the
    group manager owns their pinning via ``SuperblockGroups.install``):

      * every BN-row tile of the new superblock whose rows sit consecutively
        inside one aligned segment of the OLD superblock is copied
        device-to-device by ``kernels.ops.segment_move`` (one launch per
        SMEM-sized slice of the tile plan) — these tiles never cross the
        host link again;
      * only the remaining tiles (rows migration moved across partition
        boundaries, plus genuinely new rows) are packed into a small delta
        block and uploaded.

    What is (and is not) delta-proportional: the host→device TRANSFER and
    the per-delta-tile python work scale with the delta; the host mirror is
    still assembled in full (one vectorized O(ΣR×D) numpy pass — the same
    memcpy bound as ``build_superblock``, just sourced from the old host
    copy + delta so it stays bit-identical to the device result).  Returns
    (new_superblock, stats); ``install`` slots the result into the store's
    epoch cache (under the old superblock's cache key) so the next wave
    hits.

    ``use_kernel=None`` resolves to "is the old device buffer live?" — NOT
    the backend probe: if a copy is pinned on device (interpret mode
    included), dropping it for a full re-upload is exactly the naive cost
    this path exists to avoid; if none is pinned (host-tier store), there
    is nothing to reuse and the migration stays host-side."""
    # fires before any assembly: the old superblock (host + device copy) is
    # still whole, so callers can degrade to a lazy rebuild-on-next-touch
    fault_point("migrate.superblock", store)
    t0 = time.perf_counter()
    if use_kernel is None:
        use_kernel = old_sb._device is not None
    parts = _select_parts(store, pids)
    plan_idx = list(range(len(parts))) if pids is None \
        else [int(q) for q in pids]
    bn, row_offsets, bounds, d, d_pad, total, dtype = _superblock_layout(
        parts, old_sb.block_n)
    if d != old_sb.d:
        raise ValueError(
            f"migration changed the superblock width (d {old_sb.d}->{d}) — "
            "rebuild with build_superblock instead")
    n_tiles = total // bn
    sel = np.ones(n_tiles, np.int32)          # default: delta
    starts = np.zeros(n_tiles, np.int32)
    host = np.zeros((total, d_pad), dtype=dtype)
    delta_rows: list[np.ndarray] = []
    n_old_bounds = len(old_sb.bounds)
    # old pid -> old superblock segment slot (identity for a whole-store
    # superblock; source pids OUTSIDE a group superblock become inserts)
    if old_sb.pids is None:
        old_slot_map = np.arange(n_old_bounds, dtype=np.int64)
    else:
        old_pids = np.asarray(old_sb.pids, np.int64)
        old_slot_map = np.full(int(old_pids.max()) + 1 if len(old_pids)
                               else 0, -1, np.int64)
        old_slot_map[old_pids] = np.arange(len(old_pids))

    for g, (p, off) in enumerate(zip(parts, row_offsets)):
        i = plan_idx[g]
        r = p.block.shape[0]
        t = int((bounds[g] - off) // bn)
        if t == 0:
            continue
        # per-row source position in the OLD superblock (-1 = not there)
        src = np.full(t * bn, -1, np.int64)
        spid = np.asarray(plan.src_pid_rows[i])
        sloc = np.asarray(plan.src_loc_rows[i])
        sslot = np.full(len(spid), -1, np.int64)
        in_map = (spid >= 0) & (spid < len(old_slot_map))
        sslot[in_map] = old_slot_map[spid[in_map]]
        hit = sslot >= 0
        if hit.any():
            src[:r][hit] = old_sb.row_offsets[sslot[hit]] + sloc[hit]
        # tail-pad continuation: the padding rows of the last tile carry no
        # data, so extend the final run — the tile qualifies for a run copy
        # whose trailing reads land in the sliced-off region
        pad = t * bn - r
        if pad and r and src[r - 1] >= 0:
            src[r:] = src[r - 1] + 1 + np.arange(pad)
        chunks = src.reshape(t, bn)
        ok = chunks[:, 0] >= 0
        if bn > 1:
            ok &= np.all(np.diff(chunks, axis=1) == 1, axis=1)
        if n_old_bounds:
            s0 = chunks[:, 0]
            opid = np.clip(np.searchsorted(old_sb.bounds, s0, side="right"),
                           0, n_old_bounds - 1)
            # the whole BN-row run must stay inside ONE aligned old segment
            ok &= s0 + bn <= old_sb.bounds[opid]
        else:
            ok[:] = False
        t_base = int(off) // bn
        ok_idx = np.flatnonzero(ok)
        if len(ok_idx):
            # reused tiles: one vectorized numpy gather (python-level work
            # stays proportional to the delta loop below)
            sel[t_base + ok_idx] = 0
            starts[t_base + ok_idx] = chunks[ok_idx, 0]
            src_rows = (chunks[ok_idx, 0][:, None]
                        + np.arange(bn)).reshape(-1)
            dst_rows = (int(off) + ok_idx[:, None] * bn
                        + np.arange(bn)).reshape(-1)
            host[dst_rows] = old_sb.host[src_rows]
        for k in np.flatnonzero(~ok):
            dst = slice(int(off) + k * bn, int(off) + (k + 1) * bn)
            rows = np.zeros((bn, d_pad), dtype=dtype)
            lo = int(k) * bn
            valid = min(bn, r - lo) if r > lo else 0
            if valid > 0:
                rows[:valid, :d] = p.block[lo:lo + valid]
            starts[t_base + k] = len(delta_rows) * bn
            delta_rows.append(rows)
            host[dst] = rows

    delta = np.concatenate(delta_rows, axis=0) if delta_rows else None
    reused = int((sel == 0).sum())
    n_delta = n_tiles - reused
    bytes_uploaded = 0

    new_sb = Superblock(host=host, row_offsets=row_offsets, bounds=bounds,
                        d=d, block_n=bn,
                        epoch=int(getattr(store, "epoch", 0)),
                        pids=None if pids is None
                        else np.asarray(plan_idx, np.int64))
    used_device = bool(use_kernel) and old_sb._device is not None
    if used_device:
        import jax.numpy as jnp
        from ..kernels import ops as K
        from ..kernels.plan_launch import LANES
        if delta is None:       # all tiles reused: the kernel still needs a
            # delta operand, but a device-side fill uploads nothing
            delta_dev = jnp.zeros((bn * new_sb.row_lanes, LANES), dtype=dtype)
        else:
            delta_dev = jnp.asarray(delta.reshape(-1, LANES))
            bytes_uploaded = delta.nbytes
        new_sb._device = K.segment_move(old_sb._device, delta_dev,
                                        sel, starts, block_n=bn,
                                        row_lanes=new_sb.row_lanes)
        new_sb.uploads = 1 if bytes_uploaded else 0
        new_sb.bytes_uploaded = bytes_uploaded

    if install and pids is None:
        key = getattr(old_sb, "cache_key", None) or (None,)
        new_sb.cache_key = key
        cache = getattr(store, "_superblock_cache", None)
        if cache is None:
            cache = {}
            try:
                store._superblock_cache = cache
            except AttributeError:
                cache = None
        if cache is not None:
            cache[key] = new_sb
    stats = MigrationStats(
        n_tiles=n_tiles, reused_tiles=reused, delta_tiles=n_delta,
        bytes_uploaded=int(bytes_uploaded), bytes_total=int(host.nbytes),
        used_device=used_device, wall_s=time.perf_counter() - t0)
    return new_sb, stats


# ------------------------------------- commit ingestion: in-place append --

def extend_superblock_after_commit(store, old_sb: Superblock,
                                   touched_old_grids: dict, *,
                                   pids: Optional[Sequence[int]] = None,
                                   use_kernel: Optional[bool] = None
                                   ) -> tuple[Superblock, MigrationStats]:
    """Grow a superblock IN PLACE after a commit wave: reuse the OLD device
    buffer, upload only the new BN-aligned tiles.

    Called AFTER ``commit_version``/``commit_many`` swapped the store, with
    the PRE-commit superblock and ``touched_old_grids`` — the pre-commit
    ``grids`` array per touched partition SLOT (``store.partitions``
    index).  Commits only GROW partitions (existing rows keep their grids;
    new rids interleave into the sorted grid set), so every post-commit row
    either maps to an old superblock row (searchsorted against the old
    grids) or is new:

      * BN-row tiles whose rows sit consecutively inside one aligned old
        segment are device-to-device copies (``kernels.ops.segment_append``
        sel 0 — untouched partitions reuse ALL their tiles);
      * tiles holding any new/shifted row ride a small host delta (sel 1 —
        the only bytes a commit wave sends over the link);
      * freshly aligned all-pad tiles zero-fill on device (sel 2 — no
        upload, no source read).

    ``pids`` selects a partition GROUP (the new superblock covers those
    slots); None extends a whole-store superblock — a commit that opened a
    brand-new partition appends it as an all-delta segment.  Raises
    ValueError when the commit changed the width (d) — callers
    degrade to eviction + lazy rebuild.  Returns (new_sb, stats);
    ``stats.bytes_uploaded`` is the delta bytes the acceptance gate bounds.
    """
    # fires before ANY work — the old superblock (host + device copy) and
    # the group manager's accounting are untouched, so the caller degrades
    # to evicting just this group
    fault_point("ingest.append", store)
    t0 = time.perf_counter()
    parts_idx = (list(range(len(store.partitions))) if pids is None
                 else [int(q) for q in pids])
    parts = [store.partitions[q] for q in parts_idx]
    bn, row_offsets, bounds, d, d_pad, total, dtype = _superblock_layout(
        parts, old_sb.block_n)
    if d != old_sb.d:
        raise ValueError(
            f"commit changed the superblock width (d {old_sb.d}->{d}) — "
            "rebuild with build_superblock instead")
    n_tiles = total // bn
    sel = np.ones(n_tiles, np.int32)          # default: delta
    starts = np.zeros(n_tiles, np.int32)
    host = np.zeros((total, d_pad), dtype=dtype)
    delta_rows: list[np.ndarray] = []
    n_old_seg = len(old_sb.row_offsets)
    for g, (p, off) in enumerate(zip(parts, row_offsets)):
        q = parts_idx[g]
        r = p.block.shape[0]
        t = int((bounds[g] - off) // bn)
        if t == 0:
            continue
        # per-row source position in the OLD superblock (-1 = new row)
        src = np.full(t * bn, -1, np.int64)
        if g < n_old_seg:
            old_off = int(old_sb.row_offsets[g])
            if q not in touched_old_grids:
                # untouched partition: identical block, identity mapping
                src[:r] = old_off + np.arange(r)
            else:
                og = np.asarray(touched_old_grids[q], np.int64)
                if len(og):
                    pos = np.clip(np.searchsorted(og, p.grids), 0,
                                  len(og) - 1)
                    hit = og[pos] == p.grids
                    src[:r][hit] = old_off + pos[hit]
        # tail-pad continuation (see migrate_superblock): the padding rows
        # of the last tile carry no data, so extend the final run
        pad = t * bn - r
        if pad and r and src[r - 1] >= 0:
            src[r:] = src[r - 1] + 1 + np.arange(pad)
        chunks = src.reshape(t, bn)
        ok = chunks[:, 0] >= 0
        if bn > 1:
            ok &= np.all(np.diff(chunks, axis=1) == 1, axis=1)
        if n_old_seg:
            s0 = chunks[:, 0]
            opid = np.clip(np.searchsorted(old_sb.bounds, s0, side="right"),
                           0, n_old_seg - 1)
            # the whole BN-row run must stay inside ONE aligned old segment
            ok &= s0 + bn <= old_sb.bounds[opid]
        else:
            ok[:] = False
        t_base = int(off) // bn
        ok_idx = np.flatnonzero(ok)
        if len(ok_idx):
            sel[t_base + ok_idx] = 0
            starts[t_base + ok_idx] = chunks[ok_idx, 0]
            src_rows = (chunks[ok_idx, 0][:, None]
                        + np.arange(bn)).reshape(-1)
            dst_rows = (int(off) + ok_idx[:, None] * bn
                        + np.arange(bn)).reshape(-1)
            host[dst_rows] = old_sb.host[src_rows]
        for k in np.flatnonzero(~ok):
            lo = int(k) * bn
            valid = min(bn, r - lo) if r > lo else 0
            if valid <= 0:
                sel[t_base + k] = 2     # alignment slack: zero-fill on
                continue                # device, upload nothing
            rows = np.zeros((bn, d_pad), dtype=dtype)
            rows[:valid, :d] = p.block[lo:lo + valid]
            starts[t_base + k] = len(delta_rows) * bn
            delta_rows.append(rows)
            host[int(off) + lo:int(off) + lo + bn] = rows

    delta = np.concatenate(delta_rows, axis=0) if delta_rows else None
    reused = int((sel == 0).sum())
    n_delta = int((sel == 1).sum())
    bytes_uploaded = 0
    new_sb = Superblock(host=host, row_offsets=row_offsets, bounds=bounds,
                        d=d, block_n=bn,
                        epoch=int(getattr(store, "epoch", 0)),
                        pids=None if pids is None
                        else np.asarray(parts_idx, np.int64))
    used_device = (old_sb._device is not None if use_kernel is None
                   else bool(use_kernel) and old_sb._device is not None)
    if used_device:
        import jax.numpy as jnp
        from ..kernels import ops as K
        from ..kernels.plan_launch import LANES
        if delta is None:
            delta_dev = jnp.zeros((bn * new_sb.row_lanes, LANES), dtype=dtype)
        else:
            delta_dev = jnp.asarray(delta.reshape(-1, LANES))
            bytes_uploaded = delta.nbytes
        new_sb._device = K.segment_append(old_sb._device, delta_dev,
                                          sel, starts, block_n=bn,
                                          row_lanes=new_sb.row_lanes)
        new_sb.uploads = 1 if bytes_uploaded else 0
        new_sb.bytes_uploaded = bytes_uploaded
    stats = MigrationStats(
        n_tiles=n_tiles, reused_tiles=reused, delta_tiles=n_delta,
        bytes_uploaded=int(bytes_uploaded), bytes_total=int(host.nbytes),
        used_device=used_device, wall_s=time.perf_counter() - t0)
    return new_sb, stats


def refresh_superblocks_after_commit(store, touched_old_grids: dict, *,
                                     extend: bool = True,
                                     use_kernel: Optional[bool] = None
                                     ) -> dict:
    """Targeted post-commit superblock maintenance — the commit-path
    replacement for ``evict_superblocks``'s nuke-everything.

    ``touched_old_grids`` maps each partition SLOT the commit grew to its
    PRE-commit ``grids``.  Policy, per cached superblock:

      * a pinned group whose partitions the commit did NOT touch is
        revalidated at the new epoch in place — zero work, zero upload
        (commits only grow the receiving partitions; untouched slots keep
        their exact blocks).  Cold groups stay pinned unless a touched
        group's growth needs their bytes: then they LRU-evict to fit it;
      * a touched superblock (group or whole-store) is extended in place
        via ``extend_superblock_after_commit`` — only the new BN-aligned
        tiles cross the host link.  Touched groups are protected from
        each other's growth; on any failure (width change, a budget the
        cold groups cannot make room in, injected ``ingest.append``
        fault) THAT superblock alone degrades to eviction + lazy rebuild;
      * genuinely stale entries (pre-dating the commit's epoch) are
        evicted as before.

    Absorbs nothing itself — callers (``commit_version``/``commit_many``)
    wrap it in the same warn-and-continue guard the old eviction had.
    Returns a report dict: revalidated/extended/evicted counts plus the
    wave's bytes_uploaded and delta_tiles."""
    report = {"revalidated": 0, "extended": 0, "evicted": 0,
              "bytes_uploaded": 0, "delta_tiles": 0}
    epoch = int(getattr(store, "epoch", 0))
    touched = set(int(s) for s in touched_old_grids)
    cache = getattr(store, "_superblock_cache", None)
    evicted = 0
    if cache:
        for ck in list(cache):
            sb = cache[ck]
            if sb.epoch == epoch - 1 and extend:
                try:
                    new_sb, st = extend_superblock_after_commit(
                        store, sb, touched_old_grids,
                        use_kernel=use_kernel)
                except Exception:
                    cache.pop(ck)._device = None
                    evicted += 1
                    logger.warning(
                        "in-place superblock append failed; whole-store "
                        "copy rebuilds lazily", exc_info=True)
                    continue
                new_sb.cache_key = ck
                cache[ck] = new_sb
                sb._device = None
                report["extended"] += 1
                report["bytes_uploaded"] += st.bytes_uploaded
                report["delta_tiles"] += st.delta_tiles
            else:
                cache.pop(ck)._device = None
                evicted += 1
    if evicted:
        try:
            store._superblock_evictions = \
                getattr(store, "_superblock_evictions", 0) + evicted
        except AttributeError:
            pass
        report["evicted"] += evicted
    mgr = getattr(store, "_superblock_groups", None)
    if mgr is None:
        return report
    # only the groups this wave grows are protected: a touched group's
    # growth LRU-evicts COLD groups to fit the budget, never another
    # touched group (protecting every pinned group made a full budget
    # evict the grown group itself)
    kept: set[tuple] = set(
        k for k, sb in mgr.groups.items()
        if sb.epoch == epoch - 1 and set(k) & touched)
    for key in list(mgr.groups):
        sb = mgr.groups.get(key)
        if sb is None:          # a _make_room below already evicted it
            kept.discard(key)
            continue
        if sb.epoch != epoch - 1:
            mgr._evict(key)
            report["evicted"] += 1
            continue
        if not (set(key) & touched):
            # cold group: no member grew, its bytes are still exact —
            # revalidate at the new epoch, zero work, stays pinned
            sb.epoch = epoch
            report["revalidated"] += 1
            continue
        if not extend:
            kept.discard(key)
            mgr._evict(key)
            report["evicted"] += 1
            continue
        try:
            need = estimate_superblock_bytes(
                store, block_n=mgr.block_n, pids=key)
            grow = need - int(sb.host.nbytes)
            if grow > 0 and not mgr._make_room(grow, protected=kept):
                raise ValueError(
                    f"grown group {key} no longer fits the budget")
            new_sb, st = extend_superblock_after_commit(
                store, sb, touched_old_grids, pids=key,
                use_kernel=use_kernel)
        except Exception:
            kept.discard(key)
            if key in mgr.groups:
                mgr._evict(key)
            report["evicted"] += 1
            mgr.append_evictions += 1
            logger.warning("in-place group superblock append failed; "
                           "group rebuilds lazily on next touch",
                           exc_info=True)
            continue
        # swap in place: len(groups) unchanged, so pins - evictions still
        # equals the pinned-group count; LRU position is preserved
        new_sb.cache_key = key
        mgr.groups[key] = new_sb
        mgr.group_bytes[key] = int(new_sb.host.nbytes)
        mgr.pinned_bytes += int(new_sb.host.nbytes) - int(sb.host.nbytes)
        sb._device = None
        mgr.extended += int(st.used_device)
        report["extended"] += 1
        report["bytes_uploaded"] += st.bytes_uploaded
        report["delta_tiles"] += st.delta_tiles
    return report


# ------------------------------------------------------------- entry points --

def checkout_partitioned(store, vids: Sequence[int], *,
                         use_kernel: Optional[bool] = None,
                         engine: str = "wave",
                         device_out: bool = False):
    """Batched checkout over a PartitionedCVD, results in request order.

    engine="wave" (default): ONE fused gather for the whole wave via the
    device-resident superblock — one gather (``kernels.plan_launch``: a
    pallas_call per SMEM-sized plan slice) regardless of how many
    partitions the vids span.  engine="perpart": the previous one fused
    gather PER PARTITION (kept as oracle and benchmark baseline).

    ``device_out=True`` returns a ``WaveResult`` handle (kernel-tier wave
    gathers stay device-resident and in flight; perpart/host results ride
    the handle pre-materialized) — the serve pipeline's dispatch hook.
    """
    if engine == "wave":
        return checkout_wave(store, vids, use_kernel=use_kernel,
                             device_out=device_out)
    if engine == "perpart":
        mats = checkout_partitioned_perpart(store, vids,
                                            use_kernel=use_kernel)
        return WaveResult.from_mats(mats) if device_out else mats
    raise ValueError(f"unknown engine {engine!r} (use 'wave' or 'perpart')")


def checkout_partitioned_perpart(store, vids: Sequence[int], *,
                                 use_kernel: Optional[bool] = None,
                                 stages: Optional[WaveStages] = None
                                 ) -> list[np.ndarray]:
    """Per-partition engine: one fused gather (one launch) per partition
    touched by the wave — the baseline the wave engine is benchmarked
    against.  Each partition block the kernel uploads adds its bytes to
    ``stages.h2d_bytes``."""
    vids = _validate_vids(store, vids)
    by_pid: dict[int, list[int]] = {}
    for i, v in enumerate(vids):
        by_pid.setdefault(int(store.vid_to_pid[v]), []).append(i)
    out: list[Optional[np.ndarray]] = [None] * len(vids)
    for pid, req_idx in by_pid.items():
        p = store.partitions[pid]
        rls = [p.local_rlist(vids[i]) for i in req_idx]
        mats = checkout_rlists(p.block, rls, use_kernel=use_kernel,
                               stages=stages)
        for i, m in zip(req_idx, mats):
            out[i] = m
    return out  # type: ignore[return-value]


def checkout_versions_loop(graph: BipartiteGraph, data: np.ndarray,
                           vids: Sequence[int]) -> list[np.ndarray]:
    """Seed path: one gather per version — the oracle for the fused engine."""
    return [data[graph.rlist(int(v))] for v in vids]
