"""Static-shape partition plans: ONE planning layer for every execution path.

The paper's partitioning step (distribute nonzeros across SMs by sparsity
and dimensions) used to be re-derived ad hoc in three places — kernel slab
packing (`kernels.ops`), serving bucket padding (`serve.buckets`), and
per-device splits (`core.distributed`) — each with data-dependent shapes
that blocked composition with ``jax.vmap`` and ``shard_map``.  Following
the multi-GPU extension of this planning step (AMPED, arXiv 2507.15121)
and the fixed-granule load balancing of Nisa et al. (arXiv 1904.03329),
this module commits to **static-shape partition artifacts decided once**:

    ModeLayout / bucket class
            |
       PartitionPlan            (this module: cost model -> static caps)
            |
    +-------+-------------------+----------------------+
    | Pallas packing            | vmapped batch        | shard_map shards
    | (kernels.ops.pack_layout  | (serve.batched_engine| (core.distributed
    |  padded to slab_cap)      |  stacks bucket-mates)|  psum partials)
    +---------------------------+----------------------+

Three static quantities make the composition work:

  * ``quantize_nnz`` — the nnz cap of a (shape, nnz-bucket) request class.
    ``serve.buckets.BucketPolicy`` delegates here, so padding policy and
    kernel packing can never disagree on what a bucket holds.
  * ``slab_cap``     — an nnz-independent upper bound on the packed grid
    size: any tensor with ``nnz <= nnz_cap`` packs into at most
    ``ceil(I_d / block_rows) + nnz_cap // tile`` slabs.  Packing padded up
    to this cap (appended all-zero slabs on the last row block) is
    bit-identical to the unpadded packing and gives every bucket-mate the
    SAME array shapes — which is exactly what lets ``jax.vmap`` stack the
    Pallas backend.
  * ``DeviceShards`` — per-device rectangular slices of a mode layout
    (nnz padded to a common per-device cap) with *global* relabeled rows,
    so every device computes a partial MTTKRP into the full (I_d, R)
    output and a single ``psum`` combines them under ``shard_map``.

The tiling decisions themselves stay in the cost model
(`kernels.ops.estimate_pack_cost` / ``auto_tiles`` / ``auto_rank_block``);
this module is the single front door that consults it.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

from ..kernels import ops as kops
from ..obs import trace as obs_trace
from .load_balance import Scheme

# Per-device nnz shards are padded up to a multiple of this, so tensors of
# similar size reuse the same distributed executable.
DEVICE_SHARD_QUANTUM = 64


# ---------------------------------------------------------------------------
# nnz quantization (the bucket <-> packing contract)
# ---------------------------------------------------------------------------


def quantize_nnz(nnz: int, *, mode: str = "quantum", quantum: int = 128,
                 growth: float = 1.25, min_cap: int = 128) -> int:
    """Round ``nnz`` up to its bucket cap.  This is THE quantization rule:
    ``serve.buckets.BucketPolicy`` calls it for padding policy and
    ``plan_bucket`` consumes its output for slab caps, so the two can
    never disagree.

    mode 'quantum': next multiple of ``quantum`` (linear executable count,
    worst-case padding quantum/cap).  mode 'geometric': next
    ``min_cap * growth^k`` (bounded executable count for arbitrary
    spreads, up to (1 - 1/growth) padding).
    """
    nnz = max(int(nnz), 1)
    if mode == "quantum":
        q = max(int(quantum), 1)
        return max(-(-nnz // q) * q, min_cap)
    if mode == "geometric":
        cap = float(min_cap)
        while cap < nnz:
            cap *= growth
        return int(np.ceil(cap))
    raise ValueError(f"unknown bucketing mode {mode!r}")


def session_cap(nnz: int, current_cap: int, policy) -> int:
    """Monotone per-session bucket cap: quantize ``nnz`` through
    ``policy`` (any object with an ``nnz_cap(nnz)`` rule, i.e. a
    ``serve.buckets.BucketPolicy``) but never below the session's
    ``current_cap``.  A streaming session's fit-time nnz is pinned to its
    largest-seen executable class: shrinking the cap after an eviction
    would present NEW (smaller) array shapes to the engine and retrace —
    the exact cost the quantization exists to avoid — whereas holding the
    old cap merely keeps some already-compiled zero-weight padding slots.
    With geometric bucketing, a session therefore compiles O(log peak
    nnz) executables over its whole lifetime."""
    return max(int(current_cap), int(policy.nnz_cap(nnz)))


def slab_cap(num_rows: int, nnz_cap: int, block_rows: int, tile: int) -> int:
    """Static upper bound on the packed grid size G for ANY tensor of this
    mode with ``nnz <= nnz_cap``:  every row block contributes at least one
    slab (``ceil(I_d / block_rows)`` total) and the data itself at most
    ``floor(nnz_cap / tile)`` extra full slabs, since
    ``ceil(x / t) <= 1 + floor(x / t)``.  Packing padded to this cap makes
    the slab arrays' shapes a pure function of the bucket class."""
    nb = max(1, -(-int(num_rows) // int(block_rows)))
    return nb + int(nnz_cap) // int(tile)


# ---------------------------------------------------------------------------
# Per-mode plans (the cost model's single front door)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ModePlan:
    """Static packing/tiling decision for one output mode of a bucket class.

    Every field is a pure function of (shape, nnz_cap, rank, kappa) — no
    tensor data — so all bucket-mates share it, and it doubles as an
    executable-cache key component."""

    mode: int
    num_rows: int
    block_rows: int
    tile: int
    rank_block: int            # columns resident per kernel pass
    num_row_blocks: int
    slab_cap: int              # padded grid size G_cap (static)
    nnz_cap: int
    # Segment-backend partitioning decision for this mode: how many
    # partitions the mode layout is split into and under which
    # load-balancing scheme ('index' / 'nnz'; None = the paper's adaptive
    # threshold rule).  Defaults reproduce the caller's kappa untouched;
    # an OBSERVED density profile routes through the cost chooser
    # (``choose_segment_partition``) instead, so a skewed stream can move
    # the bucket onto a different kappa/scheme than the uniform prior
    # would pick.
    seg_kappa: int = 1
    seg_scheme: str | None = None

    @property
    def pallas_meta(self) -> tuple[int, int, int, int]:
        """The static tuple the fused sweep builder keys its cache on."""
        return (self.num_row_blocks, self.block_rows, self.tile,
                self.rank_block)


@dataclasses.dataclass(frozen=True)
class PartitionPlan:
    """All-modes static plan for one (shape, nnz_cap) class.

    Built once per bucket class (``plan_bucket``, cached) or once per
    tensor (``plan_tensor``); consumed by kernel packing, the vmapped
    batched engine, and the distributed shard builder."""

    shape: tuple[int, ...]
    nnz_cap: int
    rank: int
    kappa: int
    modes: tuple[ModePlan, ...]

    @property
    def nmodes(self) -> int:
        return len(self.shape)

    def pallas_meta(self) -> tuple:
        return tuple(m.pallas_meta for m in self.modes)

    def describe(self) -> str:
        """One-line plan fingerprint for benchmark attribution."""
        parts = []
        for m in self.modes:
            parts.append(f"m{m.mode}:br{m.block_rows}/t{m.tile}"
                         f"/rb{m.rank_block}/G{m.slab_cap}")
        return ";".join(parts)


class _UniformModeStats:
    """Duck-typed stand-in for a ``ModeLayout`` in the cost model when no
    tensor data exists yet (bucket-level planning): ``nnz_cap`` nonzeros
    spread uniformly over the mode's rows.  Exposes exactly the attributes
    ``kernels.ops.estimate_pack_cost`` consumes."""

    def __init__(self, shape: tuple[int, ...], mode: int, nnz: int):
        self.shape = tuple(int(s) for s in shape)
        self.mode = int(mode)
        self.num_rows = self.shape[mode]
        self.nnz = int(nnz)
        self.nmodes = len(self.shape)
        self.row_ptr = np.round(
            np.linspace(0.0, self.nnz, self.num_rows + 1)
        ).astype(np.int64)

    def input_modes(self):
        return [w for w in range(self.nmodes) if w != self.mode]


DENSITY_BINS = 8

# Segment-backend partition chooser (relative cost units of "one nnz of
# segmented-reduction work"): per-partition fixed overhead and per-output-row
# combine cost.  beta makes the optimal kappa finite (uniform loads would
# otherwise always want more partitions); gamma prices scheme 2's
# overlapping-output reduction against scheme 1's partition-local outputs.
SEG_PART_OVERHEAD = 16.0     # beta: nnz-equivalents per extra partition
SEG_COMBINE_COST = 1.0       # gamma: nnz-equivalents per combined output row


class _ObservedModeStats(_UniformModeStats):
    """Bucket-planning stand-in built from an OBSERVED row-density profile
    instead of the uniform prior: ``profile`` is the fraction of nnz mass
    in each of ``DENSITY_BINS`` equal row-count bins of the
    descending-sorted row loads (``serve.metrics`` accumulates it per
    bucket from real flushed batches).  Rows within a bin share its mass,
    so ``row_ptr`` reproduces the stream's skew at bin granularity and
    the cost model prices candidate tilings against what the bucket
    actually serves — the feedback loop that stops skewed streams being
    priced against a uniform distribution.

    Note the resulting ``slab_cap`` stays the data-independent worst-case
    bound (it is a function of the CHOSEN tiling only), so every bucket
    member still packs within the plan regardless of its true skew — the
    profile shifts the tiling *choice*, never the validity envelope."""

    def __init__(self, shape, mode, nnz, profile):
        super().__init__(shape, mode, nnz)
        masses = np.asarray(profile, dtype=np.float64)
        if masses.ndim != 1 or masses.size != DENSITY_BINS:
            raise ValueError(
                f"density profile must have {DENSITY_BINS} bins, got "
                f"{masses.shape}")
        masses = np.maximum(masses, 0.0)
        total = masses.sum()
        masses = (masses / total) if total > 0 else np.full(
            DENSITY_BINS, 1.0 / DENSITY_BINS)
        # Spread each bin's mass uniformly over its rows (descending-
        # sorted order — layouts relabel rows anyway, so the sorted
        # profile is the canonical representation).
        edges = np.round(np.linspace(0, self.num_rows,
                                     DENSITY_BINS + 1)).astype(np.int64)
        loads = np.zeros(self.num_rows, dtype=np.float64)
        for b in range(DENSITY_BINS):
            lo, hi = edges[b], edges[b + 1]
            if hi > lo:
                loads[lo:hi] = masses[b] * self.nnz / (hi - lo)
        row_ptr = np.zeros(self.num_rows + 1, dtype=np.float64)
        np.cumsum(loads, out=row_ptr[1:])
        self.row_ptr = np.round(row_ptr).astype(np.int64)


def density_profile(indices: np.ndarray, shape, mode: int,
                    bins: int = DENSITY_BINS) -> tuple[float, ...]:
    """Observed row-density profile of one tensor along ``mode``: fraction
    of nnz mass per equal-row-count bin of the DESCENDING-sorted row
    loads.  The serving metrics EWMA these per bucket class and feed them
    back into ``plan_bucket``."""
    num_rows = int(shape[mode])
    counts = np.sort(np.bincount(indices[:, mode],
                                 minlength=num_rows))[::-1]
    total = counts.sum()
    if total == 0:
        return tuple([1.0 / bins] * bins)
    edges = np.round(np.linspace(0, num_rows, bins + 1)).astype(np.int64)
    return tuple(
        float(counts[edges[b]:edges[b + 1]].sum() / total)
        for b in range(bins)
    )


def _lpt_makespan(loads: np.ndarray, kappa: int) -> float:
    """Max partition load of the greedy LPT assignment of descending
    ``loads`` onto ``kappa`` partitions — the same rule
    ``load_balance.partition_mode`` executes, priced here without
    building a layout."""
    if kappa <= 1:
        return float(loads.sum())
    import heapq

    heap = [0.0] * kappa
    for v in loads:
        heapq.heapreplace(heap, heap[0] + float(v))
    return float(max(heap))


def choose_segment_partition(stats, kappa_max: int) -> tuple[int, str]:
    """Pick (kappa, scheme) for the segment backend from a mode's row-load
    distribution (observed ``_ObservedModeStats`` or the uniform prior).

    Cost model, in units of one nnz of segmented-reduction work:

      scheme 'index' (1): LPT makespan over the row loads — a heavy row is
        atomic, so skew caps how far extra partitions help — plus
        ``SEG_PART_OVERHEAD`` per partition.
      scheme 'nnz' (2): perfectly balanced ``nnz/kappa`` plus
        ``SEG_COMBINE_COST`` per output row (the overlapping partial
        outputs must be combined) plus the same per-partition overhead.

    The argmin over kappa in {1, 2, 4, …, kappa_max} x both schemes is the
    bucket's segment partitioning.  With uniform loads the chosen kappa
    grows like sqrt(nnz / beta); a skewed profile plateaus the makespan at
    the heavy rows' mass, so the chooser settles on fewer partitions —
    which is exactly the observable the density feedback loop exists to
    move."""
    loads = np.sort(np.diff(stats.row_ptr))[::-1].astype(np.float64)
    nnz = float(loads.sum())
    best = (float("inf"), 1, "index")
    k = 1
    while k <= max(1, int(kappa_max)):
        over = SEG_PART_OVERHEAD * k
        c1 = _lpt_makespan(loads, k) + over
        c2 = (nnz / k
              + (SEG_COMBINE_COST * stats.num_rows if k > 1 else 0.0)
              + over)
        if c1 < best[0]:
            best = (c1, k, "index")
        if c2 < best[0]:
            best = (c2, k, "nnz")
        k *= 2
    _, k, scheme = best
    # A mode with fewer rows than partitions cannot index-partition
    # meaningfully; mirror the paper's threshold as a floor.
    if scheme == "index" and stats.num_rows < k:
        scheme = "nnz"
    return k, scheme


def _mode_plan(stats, mode: int, rank: int, nnz_cap: int,
               *, block_rows: int | None, tile: int | None,
               kappa: int = 1) -> ModePlan:
    factor_rows = [stats.shape[w] for w in stats.input_modes()]
    if block_rows is None or tile is None:
        br, t = kops.auto_tiles(stats, rank=rank, factor_rows=factor_rows)
        block_rows = block_rows if block_rows is not None else br
        tile = tile if tile is not None else t
    rblk = kops.auto_rank_block(rank, block_rows, tile, factor_rows,
                                mode=mode)
    nb = max(1, -(-stats.num_rows // block_rows))
    if isinstance(stats, _ObservedModeStats):
        # Observed density: the cost chooser decides the segment
        # partitioning (kappa is its ceiling).  Without a profile the
        # plan reproduces the caller's kappa and the adaptive scheme
        # rule untouched, so density-less paths stay bit-identical.
        seg_kappa, seg_scheme = choose_segment_partition(
            stats, max(int(kappa), DENSITY_BINS))
    else:
        seg_kappa, seg_scheme = max(1, int(kappa)), None
    return ModePlan(
        mode=mode,
        num_rows=stats.num_rows,
        block_rows=block_rows,
        tile=tile,
        rank_block=int(rblk),
        num_row_blocks=nb,
        slab_cap=slab_cap(stats.num_rows, nnz_cap, block_rows, tile),
        nnz_cap=int(nnz_cap),
        seg_kappa=seg_kappa,
        seg_scheme=seg_scheme,
    )


@functools.lru_cache(maxsize=None)
def plan_bucket(shape: tuple[int, ...], nnz_cap: int, rank: int,
                kappa: int = 1, *, block_rows: int | None = None,
                tile: int | None = None,
                density: tuple | None = None) -> PartitionPlan:
    """Static plan for a (shape, nnz_cap) bucket class — NO tensor data.

    The cost model prices each candidate tiling against a uniform nnz
    distribution by default (the only data-independent assumption
    available at bucket-planning time); ``density`` — a per-mode tuple of
    ``DENSITY_BINS`` observed row-mass fractions, fed back from
    ``serve.metrics`` — replaces the uniform prior with the stream's real
    skew.  Either way the resulting caps are valid for every member by
    construction (``slab_cap`` bounds any distribution).  Cached: all
    batches of a warm bucket class share one plan object (callers
    quantize the density profile so the cache stays small)."""
    shape = tuple(int(s) for s in shape)
    if density is not None and len(density) != len(shape):
        raise ValueError(
            f"density must carry one profile per mode ({len(shape)}), got "
            f"{len(density)}")
    # Inside the lru-cached body, so the span opens once per NOVEL bucket
    # class — a trace shows exactly which plans a stream induced (with
    # the chosen tile/rank-block/slab-cap per mode) and what each cost,
    # never the cache hits.
    with obs_trace.span("plan.build", cat="plan", shape=str(shape),
                        nnz_cap=int(nnz_cap), rank=int(rank),
                        kappa=int(kappa),
                        observed_density=density is not None) as sp:
        modes = []
        for d in range(len(shape)):
            if density is not None and density[d] is not None:
                stats = _ObservedModeStats(shape, d, nnz_cap, density[d])
            else:
                stats = _UniformModeStats(shape, d, nnz_cap)
            modes.append(_mode_plan(stats, d, rank, nnz_cap,
                                    block_rows=block_rows, tile=tile,
                                    kappa=kappa))
        plan = PartitionPlan(shape=shape, nnz_cap=int(nnz_cap),
                             rank=int(rank), kappa=int(kappa),
                             modes=tuple(modes))
        sp.set(plan=plan.describe(),
               tiles=[{"mode": m.mode, "block_rows": m.block_rows,
                       "tile": m.tile, "rank_block": m.rank_block,
                       "slab_cap": m.slab_cap} for m in plan.modes])
    return plan


def plan_layout(layout, rank: int, *, nnz_cap: int | None = None,
                block_rows: int | None = None,
                tile: int | None = None) -> ModePlan:
    """Plan one mode from a REAL layout (exact row distribution in the
    cost model).  Used by the sequential path; ``nnz_cap`` defaults to the
    layout's own nnz, i.e. no slab padding beyond the packing minimum."""
    cap = layout.nnz if nnz_cap is None else int(nnz_cap)
    return _mode_plan(layout, layout.mode, rank, cap,
                      block_rows=block_rows, tile=tile)


def plan_tensor(tensor, rank: int, kappa: int = 1, *,
                nnz_cap: int | None = None) -> PartitionPlan:
    """Per-tensor plan (bucket of one): quantizes nnz through the same
    ``quantize_nnz`` rule so a lone tensor and its bucket class agree."""
    cap = quantize_nnz(tensor.nnz) if nnz_cap is None else int(nnz_cap)
    return plan_bucket(tuple(int(s) for s in tensor.shape), cap, rank, kappa)


# ---------------------------------------------------------------------------
# Pod plans (the batch-axis shard_map path)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PodPlan:
    """How a dispatched batch of one bucket class spreads over a batch-axis
    device mesh: every device runs the SAME vmapped bucket executable on a
    ``B / num_devices`` sub-batch, so the whole pod shares one compiled
    pod block per (bucket, per-device B) class.

    ``dispatch_batch`` is the single sizing rule: the requested batch is
    rounded up to the scheduler's ``batch_quantum`` (the PR 6 executable-
    key stabilizer) and then to a mesh multiple, so ``shard_map`` slices
    the stacked arrays exactly — the padding slots are filled by
    repeating the last request (exact under vmap: independent lanes whose
    results are discarded).  The sizing is the same for every bucket;
    the bucket's kernel tiling stays with ``plan_bucket``."""

    num_devices: int
    batch_quantum: int = 1

    def dispatch_batch(self, batch: int) -> tuple[int, int]:
        """(total dispatched B, per-device sub-batch) for ``batch``
        queued requests."""
        if batch < 1:
            raise ValueError("batch must be >= 1")
        q = max(1, int(self.batch_quantum))
        tot = -(-int(batch) // q) * q
        n = max(1, int(self.num_devices))
        tot = -(-tot // n) * n
        return tot, tot // n


def pod_lane_order(nnz: list[int], num_devices: int) -> list[int]:
    """Load-aware lane placement for the pod's contiguous shard_map
    split: ``order[lane] = original request index`` such that device
    ``p`` executes lanes ``order[p*per_dev:(p+1)*per_dev]``.

    ``shard_map`` slices the stacked batch axis into contiguous
    per-device blocks, so a stream whose heavy requests cluster lands
    them all on one device.  Requests are dealt longest-processing-time
    first: descending by nnz (index-stable), each to the least-loaded
    device that still has a free lane.  The result is guaranteed no
    worse-balanced than the arrival order — if the greedy deal ever
    loses to it (possible on adversarial draws), the identity order is
    returned instead.  Identity also when the batch is not an exact
    mesh multiple (the engine pads first) or the mesh is trivial.
    """
    B = len(nnz)
    n = int(num_devices)
    identity = list(range(B))
    if n <= 1 or B == 0 or B % n:
        return identity
    per_dev = B // n
    ranked = sorted(identity, key=lambda i: (-int(nnz[i]), i))
    assign: list[list[int]] = [[] for _ in range(n)]
    loads = [0] * n
    for i in ranked:
        d = min((p for p in range(n) if len(assign[p]) < per_dev),
                key=lambda p: (loads[p], p))
        assign[d].append(i)
        loads[d] += int(nnz[i])
    order = [i for dev in assign for i in dev]
    if pod_imbalance(nnz, n, order) > pod_imbalance(nnz, n):
        return identity
    return order


def pod_device_nnz(nnz: list[int], num_devices: int,
                   order: list[int] | None = None) -> list[int]:
    """Per-device total nnz under the contiguous split of ``order``
    (identity when ``order`` is None) — the load the dispatch span and
    ``BENCH_pod.json`` record."""
    B = len(nnz)
    n = max(1, int(num_devices))
    lanes = list(range(B)) if order is None else list(order)
    per_dev = max(1, B // n)
    return [int(sum(nnz[i] for i in lanes[p * per_dev:(p + 1) * per_dev]))
            for p in range(n)]


def pod_imbalance(nnz: list[int], num_devices: int,
                  order: list[int] | None = None) -> float:
    """Max/mean per-device nnz factor (1.0 = perfectly balanced)."""
    loads = pod_device_nnz(nnz, num_devices, order)
    mean = sum(loads) / len(loads)
    return max(loads) / mean if mean > 0 else 1.0


# ---------------------------------------------------------------------------
# Per-device shards (the shard_map path)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DeviceShards:
    """Rectangular per-device arrays for one mode (leading dim = kappa).

    Rows are GLOBAL relabeled rows: every device produces a partial
    (I_d, R) output and ``psum`` combines them — scheme 1's partials have
    disjoint row support (the psum reduces to a concatenation, though it
    still pays full-array collective bandwidth — see the distributed
    module docstring), scheme 2's overlap (the analogue of the paper's
    global atomics).  Padding entries carry value 0 on row ``I_d - 1`` so
    each shard's rows stay sorted."""

    scheme: Scheme
    mode: int
    num_rows: int              # I_d
    nnz_per_dev: int           # padded nnz per device (static)
    idx: np.ndarray            # (kappa, nnz_per_dev, W) int32
    rows: np.ndarray           # (kappa, nnz_per_dev) int32 global relabeled
    vals: np.ndarray           # (kappa, nnz_per_dev) f32 (0 on padding)
    row_perm: np.ndarray       # (kappa, I_d) int32 (replicated copies)
    input_modes: tuple[int, ...]
    # Valued/weighted shards (the distributed masked path): the FULL
    # canonical coordinates of each shard entry — so a device can evaluate
    # the CP model (and hence the per-sweep residual) locally at its own
    # shard's coordinates from the replicated factors — plus per-entry
    # observation weights.  Padding entries carry weight 0, so they
    # contribute exactly +0.0 to the residual MTTKRP whatever coordinate
    # they alias (the general weight-0 mechanism).  None for value-baked
    # methods, which need neither.
    idx_full: np.ndarray | None = None   # (kappa, nnz_per_dev, N) int32
    ew: np.ndarray | None = None         # (kappa, nnz_per_dev) f32
    # Gather-collective arrays (scheme 1 only): each device's owned
    # RELABELED rows padded to a common cap, and the ORIGINAL row each
    # (device, slot) lands on — padding slots point at the dummy row I_d,
    # which the consumer slices off.  A scheme-1 partial output has
    # support only on its device's owned rows, so all-gathering just the
    # (rows_cap, R) owned slices and scattering through ``gather_map``
    # reconstructs the full factor while moving kappa*rows_cap*R floats
    # instead of the psum's kappa*I_d*R — saving ~(kappa-1)/kappa of the
    # collective payload.  None for scheme 2 (partials overlap; the psum
    # genuinely reduces).
    own_rows: np.ndarray | None = None   # (kappa, rows_cap) int32 relabeled
    gather_map: np.ndarray | None = None  # (kappa, rows_cap) int32 original

    @property
    def rows_cap(self) -> int:
        """Per-device owned-row cap of the gather collective (0 when the
        scheme does not support it)."""
        return 0 if self.own_rows is None else int(self.own_rows.shape[1])


def build_device_shards(layout, *, quantum: int = DEVICE_SHARD_QUANTUM,
                        weights: np.ndarray | None = None,
                        with_full_indices: bool = False) -> DeviceShards:
    """Slice a mode layout into kappa rectangular device shards.

    The per-device nnz cap is the max partition load rounded up to
    ``quantum`` — a static shape, so same-class tensors reuse the same
    shard_map executable.

    ``weights`` (canonical COO order) / ``with_full_indices`` populate the
    valued-shard fields consumed by the distributed masked path: each
    device then carries its entries' observation weights (0 on padding)
    and full coordinates alongside the structural arrays."""
    kappa = layout.kappa
    in_modes = layout.input_modes()
    off = layout.part_offsets
    max_nnz = int(np.diff(off).max()) if layout.nnz else 1
    cap = max(-(-max(max_nnz, 1) // quantum) * quantum, quantum)
    W = len(in_modes)
    idx = np.zeros((kappa, cap, W), np.int32)
    vals = np.zeros((kappa, cap), np.float32)
    # Padding rows sit at I_d - 1 (>= every real row in the slice), keeping
    # each shard sorted so the segmented reduction's sortedness hint holds.
    rows = np.full((kappa, cap), layout.num_rows - 1, np.int32)
    idx_full = (np.zeros((kappa, cap, layout.nmodes), np.int32)
                if with_full_indices else None)
    ew = np.zeros((kappa, cap), np.float32) if weights is not None else None
    w_lay = (np.asarray(weights, np.float32)[layout.perm]
             if weights is not None else None)
    for p in range(kappa):
        s, e = int(off[p]), int(off[p + 1])
        n = e - s
        idx[p, :n] = layout.indices[s:e][:, in_modes]
        vals[p, :n] = layout.values[s:e]
        rows[p, :n] = layout.rows[s:e]
        if idx_full is not None:
            idx_full[p, :n] = layout.indices[s:e]
        if ew is not None:
            ew[p, :n] = w_lay[s:e]
    row_perm = np.broadcast_to(
        layout.row_perm, (kappa,) + layout.row_perm.shape).copy()
    own_rows = gather_map = None
    if layout.scheme == Scheme.INDEX_PARTITION:
        # Scheme 1 partitions own disjoint contiguous relabeled ranges
        # [row_lo, row_hi): record each device's owned rows (padded to a
        # common cap by repeating an owned row — harmless, the padding
        # destination is the dummy row) and the ORIGINAL row each slot
        # scatters to (padding -> I_d, sliced off by the consumer).
        counts = (layout.row_hi - layout.row_lo).astype(np.int64)
        rcap = max(int(counts.max()) if kappa else 1, 1)
        own_rows = np.zeros((kappa, rcap), np.int32)
        gather_map = np.full((kappa, rcap), layout.num_rows, np.int32)
        for p in range(kappa):
            lo, hi = int(layout.row_lo[p]), int(layout.row_hi[p])
            n = hi - lo
            own_rows[p, :n] = np.arange(lo, hi, dtype=np.int32)
            own_rows[p, n:] = lo if n else 0
            gather_map[p, :n] = layout.row_perm[lo:hi]
    return DeviceShards(
        scheme=layout.scheme,
        mode=layout.mode,
        num_rows=layout.num_rows,
        nnz_per_dev=cap,
        idx=idx,
        rows=rows,
        vals=vals,
        row_perm=row_perm,
        input_modes=tuple(in_modes),
        idx_full=idx_full,
        ew=ew,
        own_rows=own_rows,
        gather_map=gather_map,
    )


def shard_fit_data(tensor, kappa: int, *,
                   quantum: int = DEVICE_SHARD_QUANTUM,
                   weights: np.ndarray | None = None):
    """Split the canonical COO across devices for the on-device sparse fit
    (inner product psums; zero padding contributes +0.0 exactly).

    With ``weights`` (per-entry observation weights, canonical order) the
    result is the WEIGHTED fit contract ``(idx, vals, ew, norm_sq)``:
    padding slots get weight 0, and ``norm_sq`` is the weighted
    ``sum_e w_e x_e^2`` (replicated per device) so every front door
    reports the same weighted fit."""
    nnz = tensor.nnz
    per = max(-(-max(-(-nnz // kappa), 1) // quantum) * quantum, quantum)
    idx = np.zeros((kappa, per, tensor.nmodes), np.int32)
    vals = np.zeros((kappa, per), np.float32)
    ew = np.zeros((kappa, per), np.float32) if weights is not None else None
    flat_v = tensor.values.astype(np.float32)
    flat_w = (np.asarray(weights, np.float32)
              if weights is not None else None)
    for p in range(kappa):
        s = p * per
        e = min(nnz, s + per)
        if e > s:
            idx[p, : e - s] = tensor.indices[s:e]
            vals[p, : e - s] = flat_v[s:e]
            if ew is not None:
                ew[p, : e - s] = flat_w[s:e]
    if ew is not None:
        norm_sq = np.broadcast_to(
            np.float32((flat_w * flat_v) @ flat_v), (kappa,)).copy()
        return idx, vals, ew, norm_sq
    norm_sq = np.broadcast_to(
        np.float32(tensor.norm() ** 2), (kappa,)).copy()
    return idx, vals, norm_sq
