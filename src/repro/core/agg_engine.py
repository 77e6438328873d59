"""The single implementation of Eq. 1 — one padded, backend-dispatched engine.

Previously the case-weighted FedAvg average lived in three disjoint
places: a per-leaf ``jnp.einsum`` path (``core/aggregation.py``), an
interpret-only Pallas kernel that rejected any ``N`` not divisible by
its block (``kernels/fedagg.py``), and a pure-Python scaled-copy loop on
the aggregation server that materialized one full model per site
(O(S·N) server memory).  ``AggregationEngine`` replaces all three:

  * any params pytree is raveled ONCE into a contiguous ``[S, N]`` fp32
    buffer (the ravel layout is cached per treedef/shape/dtype key),
  * ``N`` is zero-padded up to a block multiple so the Pallas ``fedagg``
    kernel accepts arbitrary parameter counts,
  * the reduction dispatches to the compiled Pallas kernel on TPU/GPU
    and to a fused ``jnp.einsum`` on CPU (tests may force either path),
  * flat and hierarchical (per-pod partials → cross-pod combine)
    reductions plus active-site masking share the same buffer.

``StreamingAccumulator`` is the host-side (numpy) counterpart for the
aggregation server: each upload is folded into a running weighted sum on
arrival, so the server holds O(N) state mid-round instead of S decoded
models — the memory term that gates scaling FL to many institutions
(cf. Sheller et al. 2020; APPFL).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.stacking import broadcast_to_sites, where_site
from repro.kernels.fedagg import fedagg as _fedagg_kernel
from repro.kernels.robust import (masked_median as _median_kernel,
                                  masked_median_ref,
                                  trimmed_mean as _trimmed_kernel,
                                  trimmed_mean_ref)

_EPS = 1e-12


def normalized_weights(case_weights: jnp.ndarray, active: jnp.ndarray,
                       scale: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """m_i/m over the active subset; zero for inactive sites.

    ``scale`` is the optional per-site Horvitz–Thompson factor from
    per-round client sampling (``repro.core.sampling``): each
    participant's weight is multiplied by ``1/π`` before the
    self-normalization, so the numerator and denominator are each
    unbiased for their dense counterparts (the Hájek estimator).
    ``None`` keeps the dense path bit-identical."""
    w = case_weights.astype(jnp.float32) * active.astype(jnp.float32)
    if scale is not None:
        w = w * scale.astype(jnp.float32)
    return w / (jnp.sum(w) + _EPS)


def per_site_nbytes(params_stacked) -> int:
    """Wire bytes of one site's uncompressed model (per-leaf dtypes) —
    the byte-accounting unit shared by the loop and scan engines."""
    return sum(int(np.prod(x.shape[1:], dtype=np.int64)) * x.dtype.itemsize
               for x in jax.tree.leaves(params_stacked))


@dataclasses.dataclass(frozen=True)
class AggregatorSpec:
    """Parsed site→global combine rule (the robust-aggregation seam).

    ``fedavg`` is Eq. 1 exactly; the rest tolerate up to ``f``
    adversarial rows.  Rank-based rules (trimmed/median/krum) are
    order statistics over the site axis: they are UNWEIGHTED over the
    active rows (case weights and rank rules don't compose — a
    100×-weighted adversary would defeat the trim) and they must see
    individual site updates, so they cannot compose with secure
    aggregation's pairwise masks.
    """
    name: str = "fedavg"       # fedavg | trimmed | median | krum | normclip
    f: int = 0                 # adversary budget (trimmed, krum)
    c: float = 0.0             # clip norm (normclip)

    @property
    def robust(self) -> bool:
        return self.name != "fedavg"

    @property
    def rank_based(self) -> bool:
        """Order-statistic rules that need the individual site rows —
        incompatible with secure-agg masks and with streaming folds."""
        return self.name in ("trimmed", "median", "krum")

    @property
    def spec(self) -> str:
        """Canonical string form (round-trips through parse_aggregator)."""
        if self.name in ("trimmed", "krum"):
            return f"{self.name}:{self.f}"
        if self.name == "normclip":
            return f"normclip:{self.c:g}"
        return self.name


FEDAVG_SPEC = AggregatorSpec()


def parse_aggregator(spec) -> AggregatorSpec:
    """``fedavg | trimmed:f | median | krum:f | normclip:c`` → spec.

    ``trimmed:0`` trims nothing, so it parses to the fedavg spec and the
    job runs the case-weighted Eq. 1 path — bit-exactness with fedavg is
    by construction, not numerical accident.  Accepts an already-parsed
    spec (idempotent) and ``None`` (fedavg).
    """
    if isinstance(spec, AggregatorSpec):
        return spec
    if spec is None:
        return FEDAVG_SPEC
    text = str(spec).strip()
    name, _, arg = text.partition(":")
    name = name.strip()
    if name in ("fedavg", "median"):
        if arg:
            raise ValueError(f"{name} takes no argument, got {text!r}")
        return FEDAVG_SPEC if name == "fedavg" else AggregatorSpec("median")
    if name in ("trimmed", "krum"):
        if not arg:
            raise ValueError(f"{name} needs an adversary budget: {name}:f")
        f = int(arg)
        if f < 0:
            raise ValueError(f"{name}:f needs f >= 0, got {text!r}")
        if f == 0 and name == "trimmed":
            return FEDAVG_SPEC
        return AggregatorSpec(name, f=f)
    if name == "normclip":
        if not arg:
            raise ValueError("normclip needs a clip norm: normclip:c")
        c = float(arg)
        if not c > 0:
            raise ValueError(f"normclip:c needs c > 0, got {text!r}")
        return AggregatorSpec("normclip", c=c)
    raise ValueError(f"unknown aggregator {text!r} (expected fedavg | "
                     "trimmed:f | median | krum:f | normclip:c)")


def krum_select(flat: jnp.ndarray, active: jnp.ndarray, f: int) -> jnp.ndarray:
    """Krum (Blanchard et al. 2017) over the active rows of [S, N].

    Each active row scores the sum of its ``m = max(k − f − 2, 1)``
    smallest squared distances to OTHER active rows (k = traced active
    count); the minimal-score row is returned verbatim.  Invalid pairs
    (self, inactive partner) enter the distance matrix at a large
    FINITE sentinel so every row's order stays total, while inactive
    rows' *scores* are +inf — the argmin therefore always lands on an
    active row, even at k = 1 where every pair is invalid but the lone
    active row's finite sentinel score still beats +inf.
    """
    x = flat.astype(jnp.float32)
    act = jnp.asarray(active).astype(jnp.float32) > 0.5
    s = x.shape[0]
    k = jnp.sum(act.astype(jnp.int32))
    sq = jnp.sum(x * x, axis=1)
    d2 = jnp.maximum(sq[:, None] + sq[None, :] - 2.0 * (x @ x.T), 0.0)
    pair_ok = act[:, None] & act[None, :] & ~jnp.eye(s, dtype=bool)
    ds = jnp.sort(jnp.where(pair_ok, d2, jnp.float32(1e30)), axis=1)
    m = jnp.minimum(jnp.maximum(k - jnp.int32(f) - 2, 1),
                    jnp.maximum(k - 1, 1))
    r = jax.lax.broadcasted_iota(jnp.int32, ds.shape, 1)
    score = jnp.sum(jnp.where(r < m, ds, 0.0), axis=1)
    score = jnp.where(act, score, jnp.inf)
    return jnp.take(x, jnp.argmin(score), axis=0)


def clip_rows(flat: jnp.ndarray, c: float) -> jnp.ndarray:
    """Row-wise L2 clip: each site's [N] row scaled by min(1, c/‖row‖).
    The ``normclip:c`` rule — bounds any single upload's pull on the
    mean without discarding it (composes with case weights)."""
    x = flat.astype(jnp.float32)
    norms = jnp.sqrt(jnp.sum(x * x, axis=1))
    factor = jnp.minimum(1.0, c / jnp.maximum(norms, _EPS))
    return x * factor[:, None]


@dataclasses.dataclass(frozen=True)
class RavelLayout:
    """How a site-stacked pytree maps into one contiguous [S, N] buffer."""
    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]    # per-leaf shapes WITHOUT the site axis
    dtypes: Tuple[Any, ...]
    offsets: Tuple[int, ...]
    n: int                                 # total flat param count


class AggregationEngine:
    """Eq. 1 for every consumer: strategies, ``global_model``, kernels API.

    ``use_pallas``/``interpret`` default to backend detection: compiled
    Pallas on TPU/GPU, jnp fallback on CPU.  Construct with
    ``use_pallas=True, interpret=True`` to exercise the kernel path under
    the Pallas interpreter (bit-faithful to the TPU program) on CPU.
    """

    def __init__(self, *, block_n: Optional[int] = None,
                 use_pallas: Optional[bool] = None,
                 interpret: Optional[bool] = None):
        self.block_n = block_n
        self.use_pallas = use_pallas
        self.interpret = interpret
        self._layouts: Dict[Any, RavelLayout] = {}

    # -- backend dispatch ---------------------------------------------------

    def _dispatch(self) -> Tuple[bool, bool]:
        backend = jax.default_backend()
        accel = backend in ("tpu", "gpu")
        use_pallas = accel if self.use_pallas is None else self.use_pallas
        interpret = (not accel) if self.interpret is None else self.interpret
        return use_pallas, interpret

    def reduce_flat(self, flat: jnp.ndarray, weights: jnp.ndarray) -> jnp.ndarray:
        """One weighted reduction over the site axis: [S, N] × [S] → [N]."""
        w = weights.astype(jnp.float32)
        use_pallas, interpret = self._dispatch()
        if use_pallas:
            return _fedagg_kernel(flat, w, block_n=self.block_n,
                                  interpret=interpret)
        return jnp.einsum("s,sn->n", w, flat.astype(jnp.float32))

    def reduce_robust_flat(self, flat: jnp.ndarray, active: jnp.ndarray,
                           spec: AggregatorSpec) -> jnp.ndarray:
        """Rank-based combine over the active rows of [S, N] → [N].

        Dispatches the trimmed/median kernels like :meth:`reduce_flat`
        dispatches ``fedagg`` (Pallas on TPU/GPU, the bit-identical jnp
        twin on CPU); krum is a [S, S] distance program with a row
        gather, so it stays jnp on every backend."""
        act = jnp.asarray(active).astype(jnp.float32)
        use_pallas, interpret = self._dispatch()
        if spec.name == "trimmed":
            if use_pallas:
                return _trimmed_kernel(flat, act, spec.f,
                                       block_n=self.block_n,
                                       interpret=interpret)
            return trimmed_mean_ref(flat, act, spec.f)
        if spec.name == "median":
            if use_pallas:
                return _median_kernel(flat, act, block_n=self.block_n,
                                      interpret=interpret)
            return masked_median_ref(flat, act)
        if spec.name == "krum":
            return krum_select(flat, act, spec.f)
        raise ValueError(f"not a rank-based rule: {spec.name}")

    # -- ravel layout (cached per treedef/shapes/dtypes) --------------------

    def layout_of(self, params_stacked) -> RavelLayout:
        leaves, treedef = jax.tree.flatten(params_stacked)
        key = (treedef, tuple(x.shape for x in leaves),
               tuple(str(x.dtype) for x in leaves))
        layout = self._layouts.get(key)
        if layout is None:
            shapes = tuple(x.shape[1:] for x in leaves)
            dtypes = tuple(x.dtype for x in leaves)
            sizes = [int(np.prod(sh, dtype=np.int64)) for sh in shapes]
            offsets = tuple(int(o) for o in np.cumsum([0] + sizes[:-1]))
            layout = RavelLayout(treedef, shapes, dtypes, offsets, sum(sizes))
            self._layouts[key] = layout
        return layout

    def flatten(self, params_stacked) -> Tuple[jnp.ndarray, RavelLayout]:
        """Ravel a site-stacked pytree into one [S, N] fp32 buffer."""
        layout = self.layout_of(params_stacked)
        leaves = jax.tree.leaves(params_stacked)
        s = leaves[0].shape[0]
        flat = jnp.concatenate(
            [x.reshape(s, -1).astype(jnp.float32) for x in leaves], axis=1)
        return flat, layout

    def unflatten(self, flat_global: jnp.ndarray, layout: RavelLayout):
        """[N] buffer → unstacked pytree, restoring per-leaf dtypes."""
        leaves = []
        for shape, dtype, ofs in zip(layout.shapes, layout.dtypes, layout.offsets):
            size = int(np.prod(shape, dtype=np.int64))
            leaves.append(flat_global[ofs: ofs + size].reshape(shape).astype(dtype))
        return jax.tree.unflatten(layout.treedef, leaves)

    def unflatten_stacked(self, flat: jnp.ndarray, layout: RavelLayout):
        """[S, N] buffer → site-stacked pytree (inverse of :meth:`flatten`).
        The round engine's buffered path round-trips params through the
        flat buffer every round, so the arrival fold can stay [S, N]."""
        s = flat.shape[0]
        leaves = []
        for shape, dtype, ofs in zip(layout.shapes, layout.dtypes, layout.offsets):
            size = int(np.prod(shape, dtype=np.int64))
            leaves.append(flat[:, ofs: ofs + size]
                          .reshape((s,) + shape).astype(dtype))
        return jax.tree.unflatten(layout.treedef, leaves)

    # -- Eq. 1 entry points -------------------------------------------------

    def global_mean(self, params_stacked, weights: jnp.ndarray):
        """Σ_s weights_s · params_s (weights already normalized) → pytree."""
        flat, layout = self.flatten(params_stacked)
        return self.unflatten(self.reduce_flat(flat, weights), layout)

    def aggregate(self, params_stacked, case_weights: jnp.ndarray,
                  active: Optional[jnp.ndarray] = None,
                  scale: Optional[jnp.ndarray] = None,
                  aggregator: Optional[AggregatorSpec] = None):
        """Eq. 1 (or a robust combine).  Returns (new stacked params,
        global params): the global model broadcast to active sites;
        inactive sites keep their local weights (the "disconnect"
        scenario).  ``scale`` threads the client-sampling
        inclusion-probability reweighting into the weights (see
        :func:`normalized_weights`); the broadcast mask stays the bool
        ``active``.  ``aggregator`` swaps the combine: rank rules
        (trimmed/median/krum) replace the weighted mean outright
        (unweighted over active rows, ``scale`` ignored); ``normclip``
        row-clips before the usual weighted fold."""
        s = jax.tree.leaves(params_stacked)[0].shape[0]
        if active is None:
            active = jnp.ones((s,), bool)
        spec = aggregator or FEDAVG_SPEC
        flat, layout = self.flatten(params_stacked)
        if spec.rank_based:
            gflat = self.reduce_robust_flat(flat, jnp.asarray(active), spec)
        else:
            if spec.name == "normclip":
                flat = clip_rows(flat, spec.c)
            w = normalized_weights(jnp.asarray(case_weights), active, scale)
            gflat = self.reduce_flat(flat, w)
        global_params = self.unflatten(gflat, layout)
        broadcast = broadcast_to_sites(global_params, s)
        return where_site(active, broadcast, params_stacked), global_params

    def reduce_pods_flat(self, flat: jnp.ndarray, case_weights: jnp.ndarray,
                         active: jnp.ndarray, pod_ids, num_pods: int,
                         intra: str = "fedavg",
                         inter: str = "fedavg",
                         scale: Optional[jnp.ndarray] = None) -> jnp.ndarray:
        """Two-tier Eq. 1 on the flat buffer: segment-reduce the [S, N]
        rows by pod id into per-pod partial means (a dense one-hot [P, S]
        contraction, so the padded buffer and the kernel path stay
        shape-static for arbitrary assignments), then cross-pod combine
        through :meth:`reduce_flat`.

        ``intra``/``inter`` pick each tier's combine rule: ``fedavg`` =
        case-weighted, ``uniform`` = unweighted mean over the tier's
        (active) members.  With ``fedavg`` at both tiers the result
        equals the flat reduction exactly — weighted means compose.
        """
        act = active.astype(jnp.float32)
        w = case_weights.astype(jnp.float32) * act
        if intra == "uniform":
            w = act
        if scale is not None:
            # client sampling: each participant enters its pod's partial
            # at the 1/π-scaled weight (the pod totals then carry the
            # scaled mass up to the cross-pod combine)
            w = w * scale.astype(jnp.float32)
        pod_ids = jnp.asarray(pod_ids)
        onehot = (pod_ids[None, :] == jnp.arange(num_pods)[:, None]
                  ).astype(jnp.float32)                       # [P, S]
        wp = onehot * w[None, :]                              # [P, S]
        pod_tot = jnp.sum(wp, axis=1)                         # [P]
        pod_mean = jnp.einsum("ps,sn->pn", wp / (pod_tot[:, None] + _EPS),
                              flat.astype(jnp.float32))       # [P, N]
        if inter == "uniform":
            pod_w = (pod_tot > 0).astype(jnp.float32)         # active pods
        else:
            pod_w = pod_tot
        return self.reduce_flat(pod_mean, pod_w / (jnp.sum(pod_w) + _EPS))

    def reduce_pods_robust(self, flat: jnp.ndarray, active: jnp.ndarray,
                           pod_ids, num_pods: int, spec: AggregatorSpec,
                           inter: str = "fedavg") -> jnp.ndarray:
        """Rank rule at the intra-pod tier: each pod robust-combines its
        own active members' rows (a static Python loop — P is a small
        static topology constant, so this stays traceable), then the
        per-pod partials cross-combine weighted by active member count
        (``inter='uniform'`` weights active pods equally).  A pod with
        zero active members contributes a zero row at weight 0, so it
        drops out of the cross-pod mean."""
        act = jnp.asarray(active).astype(jnp.float32)
        pod_ids = jnp.asarray(pod_ids)
        partials, counts = [], []
        for p in range(num_pods):
            member = (pod_ids == p).astype(jnp.float32) * act
            partials.append(self.reduce_robust_flat(flat, member, spec))
            counts.append(jnp.sum(member))
        pod_mean = jnp.stack(partials)                        # [P, N]
        cnt = jnp.stack(counts)                               # [P]
        if inter == "uniform":
            pod_w = (cnt > 0).astype(jnp.float32)
        else:
            pod_w = cnt
        return self.reduce_flat(pod_mean, pod_w / (jnp.sum(pod_w) + _EPS))

    def aggregate_pods(self, params_stacked, case_weights: jnp.ndarray,
                       pod_ids, num_pods: int,
                       active: Optional[jnp.ndarray] = None,
                       intra: str = "fedavg", inter: str = "fedavg",
                       scale: Optional[jnp.ndarray] = None,
                       aggregator: Optional[AggregatorSpec] = None):
        """Two-tier Eq. 1 for an arbitrary site→pod assignment: per-pod
        partial means → cross-pod combine, all through the same padded
        [S, N] buffer.  Returns (new stacked params, global params) with
        the usual active-site masking (inactive sites keep their local
        weights).  A rank-based ``aggregator`` applies at the INTRA tier
        (each pod defends against its own members — the Byzantine
        surface); ``normclip`` row-clips before the weighted tiers."""
        s = jax.tree.leaves(params_stacked)[0].shape[0]
        if active is None:
            active = jnp.ones((s,), bool)
        spec = aggregator or FEDAVG_SPEC
        flat, layout = self.flatten(params_stacked)
        if spec.rank_based:
            gflat = self.reduce_pods_robust(flat, jnp.asarray(active),
                                            pod_ids, num_pods, spec, inter)
        else:
            if spec.name == "normclip":
                flat = clip_rows(flat, spec.c)
            gflat = self.reduce_pods_flat(flat, jnp.asarray(case_weights),
                                          jnp.asarray(active), pod_ids,
                                          num_pods, intra, inter, scale=scale)
        global_params = self.unflatten(gflat, layout)
        broadcast = broadcast_to_sites(global_params, s)
        return where_site(active, broadcast, params_stacked), global_params

    def aggregate_hierarchical(self, params_stacked, case_weights: jnp.ndarray,
                               sites_per_pod: int,
                               active: Optional[jnp.ndarray] = None):
        """Contiguous-block special case of :meth:`aggregate_pods` (kept
        for the mesh-shaped callers: pod p owns sites
        [p·sites_per_pod, (p+1)·sites_per_pod))."""
        s = jax.tree.leaves(params_stacked)[0].shape[0]
        if sites_per_pod <= 0 or s % sites_per_pod:
            # a ragged tail would silently fall outside every pod's
            # one-hot row and be dropped from the mean — fail loudly,
            # as the old reshape-based path did
            raise ValueError(f"sites_per_pod={sites_per_pod} does not "
                             f"divide {s} sites; pass an explicit "
                             "assignment via aggregate_pods instead")
        pod_ids = jnp.arange(s) // sites_per_pod
        return self.aggregate_pods(params_stacked, case_weights, pod_ids,
                                   s // sites_per_pod, active)

    def aggregate_round(self, params_stacked, round_inputs, ctx):
        """Strategy ``post_exchange`` entry: flat vs two-tier is picked by
        the job's :class:`~repro.core.topology.Topology` (``ctx.topology``
        — this replaced the old ``ctx.hierarchical`` bool) and returns
        (new stacked params, global params)."""
        active = round_inputs["active"]
        # client sampling (repro.core.sampling): an optional [S] float
        # Horvitz–Thompson 1/π factor riding the round inputs; absent on
        # unsampled jobs so their trajectories stay bit-identical
        scale = round_inputs.get("weight_scale")
        spec = parse_aggregator(getattr(ctx, "aggregator", None))
        topo = ctx.topology
        with jax.named_scope("fold"):
            if topo.is_pods:
                s = jax.tree.leaves(params_stacked)[0].shape[0]
                return self.aggregate_pods(
                    params_stacked, ctx.case_weights, topo.pod_of(s),
                    topo.num_pods, active, topo.intra, topo.inter,
                    scale=scale, aggregator=spec)
            return self.aggregate(params_stacked, ctx.case_weights, active,
                                  scale=scale, aggregator=spec)


_DEFAULT_ENGINE: Optional[AggregationEngine] = None


def get_engine() -> AggregationEngine:
    """Process-wide default engine (shared ravel-layout cache)."""
    global _DEFAULT_ENGINE
    if _DEFAULT_ENGINE is None:
        _DEFAULT_ENGINE = AggregationEngine()
    return _DEFAULT_ENGINE


class StreamingAccumulator:
    """O(N)-memory running Eq. 1 sum for the aggregation server.

    ``fold`` folds one site's upload into the accumulator on arrival —
    the server never holds more than one fp32 model copy, however many
    sites report.  Incoming fp32 leaves that are *writable* (see
    ``decode_message(..., writable=True)``) are scaled in place, so a
    fold allocates nothing beyond transient non-fp32 casts.
    """

    def __init__(self):
        self._treedef = None
        self._acc: Optional[List[np.ndarray]] = None
        self._weight_total = 0.0
        self.count = 0

    @property
    def nbytes(self) -> int:
        """Resident accumulator bytes (the O(N) mid-round state)."""
        return sum(a.nbytes for a in self._acc) if self._acc else 0

    @property
    def weight_total(self) -> float:
        """Sum of the folded weights so far — a pod server reads this
        right before ``finalize`` so its leader can re-upload the partial
        at the pod's true (active-member) weight."""
        return self._weight_total

    @staticmethod
    def _scaled(x, w: np.float32) -> np.ndarray:
        x = np.asarray(x)
        if np.issubdtype(x.dtype, np.integer):
            # secure-aggregation path: masked fixed-point words sum as
            # EXACT modular uint64 arithmetic — any float scaling would
            # destroy the pairwise mask cancellation, so integer leaves
            # only ever fold at weight 1 (the site weights ride the
            # upload metadata and divide out at unmask time)
            if float(w) != 1.0:
                raise ValueError("integer (masked) uploads fold at weight "
                                 f"1.0, got {float(w)}")
            v = x.view(np.uint64) if x.dtype.itemsize == 8 \
                else x.astype(np.uint64)
            return v if v.flags.writeable else v.copy()
        if x.dtype == np.float32 and x.flags.writeable:
            return np.multiply(x, w, out=x)        # in place — no model copy
        return np.multiply(x, w, dtype=np.float32)

    def fold(self, tree, weight: float) -> None:
        w = np.float32(weight)
        leaves, treedef = jax.tree.flatten(tree)
        if self._acc is None:
            self._treedef = treedef
            self._acc = [self._scaled(x, w) for x in leaves]
        else:
            if treedef != self._treedef:
                raise ValueError("upload pytree structure changed mid-round")
            for a, x in zip(self._acc, leaves):
                np.add(a, self._scaled(x, w), out=a)
        self._weight_total += float(weight)
        self.count += 1

    @property
    def is_integer(self) -> bool:
        """True when the buffered round is a masked (fixed-point) one."""
        return bool(self._acc) and \
            np.issubdtype(self._acc[0].dtype, np.integer)

    def finalize(self):
        """Normalize by the folded weight total and return the global pytree
        (fp32 leaves).  Resets the accumulator for the next round."""
        if self._acc is None:
            return None
        if self.is_integer:
            raise ValueError("masked integer rounds finalize via "
                             "finalize_int() + SecureAggState.unmask()")
        inv = np.float32(1.0 / self._weight_total)
        leaves = [np.multiply(a, inv, out=a) for a in self._acc]
        tree = jax.tree.unflatten(self._treedef, leaves)
        self._treedef, self._acc = None, None
        self._weight_total, self.count = 0.0, 0
        return tree

    def finalize_int(self):
        """The raw modular uint64 sum of a masked round, unnormalized —
        :meth:`~repro.privacy.secure_agg.SecureAggState.unmask` recovers
        the weighted mean.  Resets the accumulator for the next round."""
        if self._acc is None:
            return None
        tree = jax.tree.unflatten(self._treedef, self._acc)
        self._treedef, self._acc = None, None
        self._weight_total, self.count = 0.0, 0
        return tree


# -- host-side (numpy) twins for the socket servers -------------------------
#
# The AggregationServer runs on plain numpy (no device round-trips in its
# handler threads).  Sanitation checks every upload on arrival; the rank
# rules re-run the same fe/keep math as kernels/robust._trim_block over a
# per-round row buffer (rank statistics need all rows at once, so the
# robust server mode trades the O(N) streaming fold for O(S·N) — the
# cost of not trusting the rows).


def tree_all_finite(tree) -> bool:
    """True iff every float leaf is NaN/Inf-free.  Integer leaves (the
    masked fixed-point uploads) are trivially finite."""
    for x in jax.tree.leaves(tree):
        a = np.asarray(x)
        if np.issubdtype(a.dtype, np.floating) and not np.isfinite(a).all():
            return False
    return True


def tree_l2_norm(tree) -> float:
    """Global L2 norm over the float leaves of an upload (float64
    accumulation so huge adversarial values don't overflow the check
    that is supposed to catch them)."""
    total = 0.0
    for x in jax.tree.leaves(tree):
        a = np.asarray(x)
        if np.issubdtype(a.dtype, np.floating):
            total += float(np.sum(a.astype(np.float64) ** 2))
    return float(np.sqrt(total))


def clip_tree_norm(tree, c: float):
    """Host twin of :func:`clip_rows` for one upload: scale the whole
    tree by min(1, c/‖tree‖).  Streaming-compatible — the server clips
    before the fold, so ``normclip`` keeps the O(N) accumulator."""
    norm = tree_l2_norm(tree)
    if norm <= c:
        return tree
    factor = np.float32(c / max(norm, _EPS))
    return jax.tree.map(
        lambda x: np.asarray(x, np.float32) * factor
        if np.issubdtype(np.asarray(x).dtype, np.floating) else x, tree)


def robust_combine_trees(trees: List[Any], spec: AggregatorSpec):
    """Host twin of the traced rank rules for the row-buffered server
    mode: the round's uploads are stacked per leaf and rank-combined
    coordinate-wise (same clamp math as ``kernels/robust._trim_block``);
    krum distances run over the concatenated ravels.  Parity with the
    traced path is allclose, not bit-exact (summation order differs).
    """
    if not trees:
        return None
    k = len(trees)
    flat_list = [jax.tree.flatten(t) for t in trees]
    treedef = flat_list[0][1]
    for _, td in flat_list[1:]:
        if td != treedef:
            raise ValueError("upload pytree structure changed mid-round")
    if spec.name == "krum":
        flats = np.stack([np.concatenate(
            [np.asarray(x, np.float32).ravel() for x in lv])
            for lv, _ in flat_list])
        sq = np.sum(flats * flats, axis=1)
        d2 = sq[:, None] + sq[None, :] - 2.0 * (flats @ flats.T)
        np.maximum(d2, 0.0, out=d2)
        np.fill_diagonal(d2, np.inf)
        m = max(min(k - spec.f - 2, k - 1), 1)
        score = np.sum(np.sort(d2, axis=1)[:, :m], axis=1)
        return trees[int(np.argmin(score))]
    f = k if spec.name == "median" else spec.f
    fe = min(f, (k - 1) // 2)
    out = []
    for i in range(len(flat_list[0][0])):
        stack = np.stack([np.asarray(lv[i], np.float32)
                          for lv, _ in flat_list])
        xs = np.sort(stack, axis=0)
        out.append(np.mean(xs[fe: k - fe], axis=0, dtype=np.float32))
    return jax.tree.unflatten(treedef, out)
