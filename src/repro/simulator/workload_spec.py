"""Declarative workload protocol: pure, batchable ``WorkloadSpec`` pytrees.

Workloads were the last pre-protocol API: stateful numpy ``for t in
range(T)`` loops host-materializing dense ``[T, n]`` float64 traces
(2 GiB per lane at n=65536, T=4096).  Here every workload is a
``WorkloadSpec`` — a pytree whose *leaves* are the scenario knobs (zipf
exponent, hot fraction, drift rate, phase windows; all f32/i32 and
batchable into sweep lanes) — with pure, jittable functions over a small
``WorkloadState`` pytree:

    state        = spec.init(n, key)
    state, probs = spec.step(state, t)       # [n] access distribution, sums to 1
    work         = spec.work_of(state, t)    # true accesses this interval

The compiled scan engine synthesizes ``true = work * probs`` on device per
interval (scan_engine.py), so per-lane trace storage drops from O(T*n) to
O(n); ``spec.materialize(T, n, seed)`` runs the same functions once and
returns the dense f32 array the numpy reference engine replays — the two
paths are bitwise-identical by construction (tests/test_workload_spec.py).

Like the policy protocol's observe/fires/policy split, the expensive
re-randomization events (hot-set relocation, zipf reshuffle, frontier
boosts) are factored out of the per-interval path:

    due   = spec.event_due(state, t)    # cheap scalar bool
    state = spec.event(state, t)        # O(n log n) redraw; masked per component
    probs = spec.probs_of(state, t)     # cheap O(n), every interval

``step`` composes them (cond(event_due) around event); the scan engine
hoists ``any(lane due)`` to a scalar ``lax.cond`` across workload lanes so
permutation redraws only run on event intervals.  Event draws are keyed by
``(seed, epoch)`` — pure functions of time, never a consumed key chain —
so gated and ungated replays cannot desync.

Internal representation
-----------------------
A spec is a stack of S *components*; every leaf is ``[S]`` (``S`` is
implied by leaf shapes, so specs compose structurally).  Each component
has a kind (zipf / hot-set / xsbench / tpcc-window / zipf+boost), its
knobs, an activity window ``[t_start, t_end)``, a duty cycle, and a
mixture weight.  The interval distribution is the rate-weighted mixture

    rate_c(t) = weight_c * active_c(t) * work_c * duty_c(t)
    probs(t)  = sum_c rate_c * p_c / sum_c rate_c,   work(t) = sum_c rate_c

which makes scenario algebra trivial: ``mix`` concatenates components and
scales weights, ``phases`` concatenates and sets activity windows,
``scale`` multiplies per-component work, ``drift`` adds a page-coordinate
shift rate.  Composed scenarios are declared, not hand-coded.

Hot sets are exact-k: rank permutations (one per component, redrawn on
events) define hot membership as ``rank < k_hot``, so ``k_hot`` stays a
*traced* knob while shapes stay static.
"""
from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.utils.pytree import pytree_dataclass

DEFAULT_PAGES = 4096      # 8 GiB RSS at 2 MB pages
DEFAULT_WORK = 2.0e7      # true accesses per interval
NEVER = 1 << 30           # i32-safe "no event" period

KIND_ZIPF, KIND_HOTSET, KIND_XSBENCH, KIND_TPCC, KIND_ZIPF_BOOST = range(5)

#: module counter: every host materialization bumps it.  The CI workload-
#: lane gate reads it to prove a synth sweep never built a [T, n] array.
MATERIALIZE_CALLS = 0


@pytree_dataclass
class WorkloadState:
    rank: jnp.ndarray      # i32 [S, n] permutation (zipf ranks / hot order)
    rank2: jnp.ndarray     # i32 [S, n] boost-set permutation (gapbs)
    base_key: jnp.ndarray  # u32 [S, 2] per-component event PRNG key


@pytree_dataclass
class WorkloadSpec:
    """Stack of S workload components; every field is a batchable leaf."""

    kind: jnp.ndarray          # i32 [S] component formula selector
    work: jnp.ndarray          # f32 [S] true accesses/interval at full duty
    weight: jnp.ndarray        # f32 [S] mixture weight
    t_start: jnp.ndarray       # i32 [S] activity window [t_start, t_end)
    t_end: jnp.ndarray         # i32 [S]
    s: jnp.ndarray             # f32 [S] zipf exponent
    hot_frac: jnp.ndarray      # f32 [S] hot-set fraction of n
    hot_weight: jnp.ndarray    # f32 [S] access mass on the hot set
    shift_every: jnp.ndarray   # i32 [S] rank-permutation redraw period
    window_frac: jnp.ndarray   # f32 [S] tpcc sliding-window fraction
    drift_pages: jnp.ndarray   # f32 [S] tpcc window drift (pages/interval)
    boost_every: jnp.ndarray   # i32 [S] gapbs boost-set redraw period
    boost_frac: jnp.ndarray    # f32 [S] gapbs boost-set fraction
    boost_gain: jnp.ndarray    # f32 [S] gapbs boost mass (pre-normalize)
    period: jnp.ndarray        # i32 [S] duty-cycle period (liblinear)
    duty: jnp.ndarray          # f32 [S] busy fraction of the period
    phase_off: jnp.ndarray     # i32 [S] duty-cycle phase offset (intervals)
    idle_scale: jnp.ndarray    # f32 [S] work multiplier when idle
    drift_rate: jnp.ndarray    # f32 [S] whole-distribution drift (combinator)
    seed: jnp.ndarray          # i32 [S] per-component randomness seed

    # ---------------------------------------------------------------- init
    def init(self, n: int, key):
        """Fresh per-component state; draws are keyed by (seed, epoch=0)."""
        bks = jax.vmap(lambda s: jax.random.fold_in(key, s))(self.seed)
        perm = lambda bk, tag: jax.random.permutation(
            jax.random.fold_in(jax.random.fold_in(bk, tag), 0), n)
        return WorkloadState(
            rank=jax.vmap(lambda bk: perm(bk, 1))(bks).astype(jnp.int32),
            rank2=jax.vmap(lambda bk: perm(bk, 2))(bks).astype(jnp.int32),
            base_key=bks)

    # -------------------------------------------------------------- events
    def event_due(self, state, t):
        """Scalar bool: does any ACTIVE component redraw a permutation at
        ``t``?  Gating on the activity window keeps inactive-phase
        components from firing wasted permutation sorts in the scan."""
        se = jnp.maximum(self.shift_every, 1)
        be = jnp.maximum(self.boost_every, 1)
        active = (t >= self.t_start) & (t < self.t_end)
        return jnp.any(active & (t > 0)
                       & (((t % se) == 0) | ((t % be) == 0)))

    def event(self, state, t, with_boost: bool = True):
        """Redraw rank permutations for due components (masked per
        component, keyed by epoch — safe to call on any interval).

        ``with_boost`` is a STATIC flag (see ``has_boost``): when no
        component can ever redraw a boost set, callers pass False and the
        second permutation sort is dropped from the program entirely —
        ``rank2`` is never read by non-boost kinds, so outputs are
        unchanged either way.
        """
        n = state.rank.shape[1]

        def upd(bk, se, be, ts, te, rank, rank2):
            se = jnp.maximum(se, 1)
            be = jnp.maximum(be, 1)
            fresh = lambda tag, epoch: jax.random.permutation(
                jax.random.fold_in(jax.random.fold_in(bk, tag), epoch),
                n).astype(jnp.int32)
            due = (t >= ts) & (t < te) & (t > 0)
            rank = jnp.where(due & ((t % se) == 0), fresh(1, t // se), rank)
            if with_boost:
                rank2 = jnp.where(due & ((t % be) == 0), fresh(2, t // be),
                                  rank2)
            return rank, rank2

        rank, rank2 = jax.vmap(upd)(state.base_key, self.shift_every,
                                    self.boost_every, self.t_start,
                                    self.t_end, state.rank, state.rank2)
        return state.replace(rank=rank, rank2=rank2)

    # ------------------------------------------------------------- mixture
    def _rates(self, t):
        """f32 [S] per-component access rate this interval."""
        f32 = jnp.float32
        active = ((t >= self.t_start) & (t < self.t_end)).astype(f32)
        per = jnp.maximum(self.period, 1)
        # phase_off staggers duty cycles across components (antiphase
        # tenants, adversarial phase flips — simulator/scenarios.py);
        # the default 0 is bitwise the historical formula.
        busy = ((t + self.phase_off) % per).astype(f32) \
            < self.duty * per.astype(f32)
        m = jnp.where(busy, f32(1.0), self.idle_scale)
        return self.weight * active * self.work * m

    def _comp_probs(self, state, t, with_drift: bool = True):
        """f32 [S, n] per-component normalized access distributions.

        ``with_drift`` is a STATIC flag (see ``has_drift``): when no
        component drifts, the shift is 0 at every interval and the rank
        rows are read as they are; otherwise they are rotated by the
        shift (``rotate``).  Both give the same ranks, bit for bit.
        """
        f32 = jnp.float32
        tf = jnp.asarray(t, f32)

        def one(kind, s, hot_frac, hot_weight, window_frac, drift_pages,
                boost_frac, boost_gain, drift_rate, rank, rank2):
            n = rank.shape[0]
            nf = f32(n)
            i = jnp.arange(n, dtype=jnp.int32)
            if with_drift:
                shift = jnp.floor(drift_rate * tf).astype(jnp.int32) % n
                idx = (i - shift) % n
                with jax.named_scope("rotate"):
                    rank, rank2 = rotate(jnp.stack([rank, rank2]), shift)
            else:
                idx = i
            r = rank.astype(f32)
            r2 = rank2.astype(f32)

            def zipf(_):
                return (r + 1.0) ** (-s)

            def hotset(_):
                # guarded cold mass keeps hot_frac=1.0 valid (legacy gups
                # divided by n - k_hot and crashed); with every page hot
                # the branch normalization yields the uniform distribution
                kh = jnp.clip(jnp.round(nf * hot_frac), 1.0, nf)
                return jnp.where(r < kh, hot_weight / kh,
                                 (1.0 - hot_weight)
                                 / jnp.maximum(nf - kh, 1.0))

            def xsb(_):
                kh = jnp.clip(jnp.round(nf * hot_frac), 1.0, nf)
                return 0.5 / nf + jnp.where(r < kh, 0.5 / kh, 0.0)

            def tpcc(_):
                # clamp keeps window_frac=1.0 valid (legacy silo_tpcc took
                # a modulo by n - w and crashed there)
                w = jnp.clip(jnp.round(nf * window_frac), 1.0, nf - 1.0)
                span = jnp.maximum(nf - w, 1.0)
                head = jnp.mod(jnp.floor(drift_pages * tf), span)
                off = idx.astype(f32) - head
                inwin = (off >= 0.0) & (off < w)
                # geometric closed form of the legacy decay normalizer
                q = jnp.exp(-2.0 / w)
                denom = jnp.where(w > 1.0, (1.0 - q ** w) / (1.0 - q), 1.0)
                dec = jnp.exp(-(w - 1.0 - off) / (w * 0.5))
                return 0.05 / nf + jnp.where(inwin, 0.95 * dec / denom, 0.0)

            def boost(_):
                m = (r + 1.0) ** (-s)
                base = m / jnp.maximum(m.sum(), 1e-30)
                nb = jnp.clip(jnp.round(nf * boost_frac), 1.0, nf)
                return base + jnp.where(r2 < nb, boost_gain / nb, 0.0)

            p = jax.lax.switch(kind, [zipf, hotset, xsb, tpcc, boost], None)
            return p / jnp.maximum(p.sum(), 1e-30)

        return jax.vmap(one)(self.kind, self.s, self.hot_frac,
                             self.hot_weight, self.window_frac,
                             self.drift_pages, self.boost_frac,
                             self.boost_gain, self.drift_rate,
                             state.rank, state.rank2)

    def probs_of(self, state, t, with_drift: bool = True):
        """f32 [n] interval access distribution (sums to 1 to f32 tol).
        ``with_drift`` is static, as in ``_comp_probs``."""
        p = self._comp_probs(state, t, with_drift)           # [S, n]
        rate = self._rates(t)                                # [S]
        tot = rate.sum()
        mix = (rate[:, None] * p).sum(axis=0) / jnp.maximum(tot, 1e-30)
        n = state.rank.shape[1]
        return jnp.where(tot > 0.0, mix, jnp.float32(1.0 / n))

    def work_of(self, state, t):
        """f32 scalar: true accesses carried by this interval."""
        return self._rates(t).sum()

    def step(self, state, t):
        """Reference composition: cond(event_due) event, then probs."""
        state = jax.lax.cond(self.event_due(state, t),
                             lambda s: self.event(s, t), lambda s: s, state)
        return state, self.probs_of(state, t)

    # --------------------------------------------------- host conveniences
    @property
    def n_components(self) -> int:
        return int(np.asarray(self.kind).shape[0])

    def max_rate(self) -> float:
        """Host-side upper bound on any page's true per-interval count
        (probs <= 1; the duty multiplier can exceed 1 via idle_scale)."""
        rate = np.abs(np.asarray(self.work) * np.asarray(self.weight)) \
            * np.maximum(np.abs(np.asarray(self.idle_scale)), 1.0)
        return float(np.sum(rate))

    def has_boost(self) -> bool:
        """Host-side: can any component ever redraw its boost set?  Lets
        the engines statically skip the second permutation draw."""
        return bool(np.any(np.asarray(self.boost_every) < NEVER))

    def has_drift(self) -> bool:
        """Host-side: can any component's drift shift be non-zero?  Lets
        the engines statically read the rank rows without rotating them."""
        return bool(np.any(np.asarray(self.drift_rate) != 0.0))

    def materialize(self, T: int, n: int, seed: int = 0) -> np.ndarray:
        """Dense f32 ``[T, n]`` trace for the numpy reference engine.

        Runs the very same jitted init/step functions the scan engine
        synthesizes from, so the rows are bitwise-identical to the
        device-synthesized counts under the same ``seed``.
        """
        global MATERIALIZE_CALLS
        MATERIALIZE_CALLS += 1
        tr = _materialize_jit(self, T, n, jax.random.PRNGKey(seed),
                              self.has_boost(), self.has_drift())
        return np.asarray(tr)


def rotate(x, shift):
    """``x[..., (arange(n) - shift) % n]`` for a traced scalar ``shift``
    in ``[0, n)``, without a gather.

    A gather indexed by a traced shift (``jnp.roll`` included, whose
    batched ``dynamic_slice`` lowers to one) reads element by element.
    Here the rotation is a barrel shift of ``log2(n)`` stages: stage
    ``b`` is a static roll by ``2**b`` (a slice and a concatenate), kept
    where bit ``b`` of ``shift`` is set.
    """
    for b in range((x.shape[-1] - 1).bit_length()):
        x = jnp.where((shift >> b) & 1 == 1, jnp.roll(x, 1 << b, axis=-1),
                      x)
    return x


@functools.partial(jax.jit,
                   static_argnames=("T", "n", "with_boost", "with_drift"))
def _materialize_jit(spec, T, n, key, with_boost, with_drift):
    cls = type(spec)

    def row(st, t):
        # same cond + split functions the scan engine inlines (step's
        # reference composition, with the static boost-draw and drift
        # flags)
        st = jax.lax.cond(cls.event_due(spec, st, t),
                          lambda s: cls.event(spec, s, t, with_boost),
                          lambda s: s, st)
        probs = cls.probs_of(spec, st, t, with_drift)
        return st, cls.work_of(spec, st, t) * probs

    _, tr = jax.lax.scan(row, spec.init(n, key), jnp.arange(T))
    return tr.astype(jnp.float32)


# --------------------------------------------------------------- builders
def _comp(kind, *, work=DEFAULT_WORK, weight=1.0, t_start=0, t_end=NEVER,
          s=0.0, hot_frac=0.0, hot_weight=0.0, shift_every=NEVER,
          window_frac=0.0, drift_pages=0.0, boost_every=NEVER,
          boost_frac=0.0, boost_gain=0.0, period=1, duty=1.0,
          phase_off=0, idle_scale=1.0, drift_rate=0.0, seed=0) -> dict:
    return dict(kind=kind, work=work, weight=weight, t_start=t_start,
                t_end=t_end, s=s, hot_frac=hot_frac, hot_weight=hot_weight,
                shift_every=max(1, int(shift_every)),
                window_frac=window_frac, drift_pages=drift_pages,
                boost_every=max(1, int(boost_every)), boost_frac=boost_frac,
                boost_gain=boost_gain, period=max(1, int(period)), duty=duty,
                phase_off=int(phase_off), idle_scale=idle_scale,
                drift_rate=drift_rate, seed=int(seed))


_F32 = ("work", "weight", "s", "hot_frac", "hot_weight", "window_frac",
        "drift_pages", "boost_frac", "boost_gain", "duty", "idle_scale",
        "drift_rate")
_I32 = ("kind", "t_start", "t_end", "shift_every", "boost_every", "period",
        "phase_off", "seed")


def _from_comps(comps: list[dict]) -> WorkloadSpec:
    cols = {}
    for f in _F32:
        cols[f] = jnp.asarray([c[f] for c in comps], jnp.float32)
    for f in _I32:
        cols[f] = jnp.asarray([c[f] for c in comps], jnp.int32)
    return WorkloadSpec(**cols)


def _to_comps(spec: WorkloadSpec) -> list[dict]:
    fields = _F32 + _I32
    cols = {f: np.asarray(getattr(spec, f)) for f in fields}
    S = cols["kind"].shape[0]
    return [{f: cols[f][c].item() for f in fields} for c in range(S)]


def with_label(spec: WorkloadSpec, label: str) -> WorkloadSpec:
    """Attach a display label (kept off the pytree; purely cosmetic)."""
    object.__setattr__(spec, "_label", label)
    return spec


def label_of(spec, default: str = "workload") -> str:
    return getattr(spec, "_label", default)


# ------------------------------------------------------- named workloads
def gups_spec(work=DEFAULT_WORK, seed=0, hot_frac=0.125, hot_weight=0.9,
              shift_every=150) -> WorkloadSpec:
    """Uniform accesses within a small hot set that relocates periodically."""
    return with_label(_from_comps([_comp(
        KIND_HOTSET, work=work, hot_frac=hot_frac, hot_weight=hot_weight,
        shift_every=shift_every, seed=seed)]), "gups")


def zipf_spec(s=0.99, work=DEFAULT_WORK, seed=1,
              shuffle_every=NEVER) -> WorkloadSpec:
    """Zipf distribution over a random permutation, optional reshuffles."""
    return with_label(_from_comps([_comp(
        KIND_ZIPF, work=work, s=s, shift_every=shuffle_every, seed=seed)]),
        "zipf")


def tpcc_spec(work=DEFAULT_WORK, seed=4, window_frac=0.15,
              drift_pages=2.0) -> WorkloadSpec:
    """"Latest" distribution: hot window slides as rows are inserted."""
    return with_label(_from_comps([_comp(
        KIND_TPCC, work=work, window_frac=window_frac,
        drift_pages=drift_pages, seed=seed)]), "silo-tpcc")


def xsbench_spec(work=DEFAULT_WORK, seed=5, hot_frac=0.02) -> WorkloadSpec:
    """Small very-hot lookup tables + uniform background over the RSS."""
    return with_label(_from_comps([_comp(
        KIND_XSBENCH, work=work, hot_frac=hot_frac, seed=seed)]), "xsbench")


def gapbs_spec(s=0.8, work=DEFAULT_WORK, seed=6, boost_every=40,
               boost_frac=0.05, boost_gain=0.3) -> WorkloadSpec:
    """Power-law degree distribution + periodic frontier boosts."""
    return with_label(_from_comps([_comp(
        KIND_ZIPF_BOOST, work=work, s=s, boost_every=boost_every,
        boost_frac=boost_frac, boost_gain=boost_gain, seed=seed)]), "gapbs")


def liblinear_spec(work=DEFAULT_WORK, seed=9, period=20, duty=0.5,
                   idle_scale=0.02) -> WorkloadSpec:
    """Periodic memory-intensive zipf sweeps alternating with near-idle
    compute phases (batched migration's best case, paper §7.2)."""
    return with_label(_from_comps([_comp(
        KIND_ZIPF, work=work, s=0.6, period=period, duty=duty,
        idle_scale=idle_scale, seed=seed)]), "liblinear")


def zipf_shuffled_spec(s=0.99, work=DEFAULT_WORK, seed=1,
                       shuffle_at=()) -> WorkloadSpec:
    """Zipf with ONE-SHOT reshuffles at the given times: each reshuffle
    switches to an independently-permuted zipf phase (``phases``
    combinator) — a reshuffle at ``v`` and nothing after, unlike the
    periodic ``shuffle_every`` knob."""
    times = sorted({int(v) for v in shuffle_at})
    children = [zipf_spec(s=s, work=work, seed=seed + 7919 * i)
                for i in range(len(times) + 1)]
    if not times:
        return children[0]
    return with_label(phases(children, times), "zipf")


def btree_spec(T: int = 400, work=DEFAULT_WORK, seed=2) -> WorkloadSpec:
    """Zipf index lookups with one hot-set reshuffle at T // 2 (Fig. 9)."""
    return with_label(zipf_shuffled_spec(
        s=0.9, work=work, seed=seed, shuffle_at=(max(1, T // 2),)), "btree")


#: name -> spec constructor taking (T, work, seed).  ``T`` only matters for
#: btree's mid-run reshuffle (legacy semantics: hot-set change at T // 2).
_NAMED = {
    "gups": lambda T, work, seed: gups_spec(work=work, seed=seed),
    "btree": lambda T, work, seed: btree_spec(T, work=work, seed=seed),
    "silo-ycsb": lambda T, work, seed: zipf_spec(
        s=0.99, work=work, seed=seed),
    "silo-tpcc": lambda T, work, seed: tpcc_spec(work=work, seed=seed),
    "xsbench": lambda T, work, seed: xsbench_spec(work=work, seed=seed),
    "gapbs-bc": lambda T, work, seed: gapbs_spec(
        s=0.8, work=work, seed=seed, boost_every=40, boost_frac=0.05,
        boost_gain=0.3),
    "gapbs-pr": lambda T, work, seed: zipf_spec(
        s=0.7, work=work, seed=seed),
    "gapbs-cc": lambda T, work, seed: gapbs_spec(
        s=0.75, work=work, seed=seed, boost_every=100, boost_frac=0.1,
        boost_gain=0.2),
    "liblinear": lambda T, work, seed: liblinear_spec(work=work, seed=seed),
}

NAMED_WORKLOADS = tuple(sorted(_NAMED))


def named(name: str, T: int = 400, work: float = DEFAULT_WORK,
          seed: int | None = None, seed_offset: int = 0) -> WorkloadSpec:
    """Spec for a paper workload by name (same seed derivation as the
    legacy ``workloads.make``: crc32 of the name, plus ``seed_offset``,
    unless an explicit ``seed`` is given)."""
    if name not in _NAMED:
        raise ValueError(f"unknown workload {name!r}; "
                         f"known: {sorted(_NAMED)}")
    if seed is None:
        seed = zlib.crc32(name.encode()) % 1000 + seed_offset
    return with_label(_NAMED[name](T, work, seed), name)


# ------------------------------------------------------------ combinators
def phases(specs: list[WorkloadSpec], boundaries: list[int],
           label: str | None = None) -> WorkloadSpec:
    """Piecewise scenario: ``specs[p]`` is active on ``[b_{p-1}, b_p)``.

    ``boundaries`` has ``len(specs) - 1`` ascending interval indices; each
    child's own activity window is intersected with its phase window, so
    nested ``phases`` compose.
    """
    if len(boundaries) != len(specs) - 1:
        raise ValueError(f"phases wants len(boundaries) == len(specs) - 1; "
                         f"got {len(boundaries)} vs {len(specs)}")
    if any(b2 <= b1 for b1, b2 in zip(boundaries, boundaries[1:])):
        raise ValueError(f"boundaries must ascend; got {boundaries}")
    if boundaries and int(boundaries[0]) < 1:
        # boundary 0 makes phase 0 a zero-length window: its spec would
        # silently never run.
        raise ValueError(f"first boundary must be >= 1; got {boundaries}")
    edges = [0] + [int(b) for b in boundaries] + [NEVER]
    comps = []
    for p, sp in enumerate(specs):
        for c in _to_comps(sp):
            c["t_start"] = max(c["t_start"], edges[p])
            c["t_end"] = min(c["t_end"], edges[p + 1])
            comps.append(c)
    return with_label(_from_comps(comps), label or "+".join(
        label_of(sp, f"p{i}") for i, sp in enumerate(specs)))


def mix(specs: list[WorkloadSpec], weights: list[float] | None = None,
        label: str | None = None) -> WorkloadSpec:
    """Blend scenarios: rate-weighted mixture of the children.  Weights
    normalize to 1 (``mix(xs, [2, 2]) == mix(xs, [1, 1])``)."""
    if weights is None:
        weights = [1.0] * len(specs)
    if len(weights) != len(specs):
        raise ValueError("mix wants one weight per spec")
    tot = float(sum(weights))
    if tot <= 0.0:
        raise ValueError("mix weights must sum > 0")
    comps = []
    for w, sp in zip(weights, specs):
        for c in _to_comps(sp):
            c["weight"] = c["weight"] * float(w) / tot
            comps.append(c)
    return with_label(_from_comps(comps), label or "mix(" + ",".join(
        label_of(sp, f"m{i}") for i, sp in enumerate(specs)) + ")")


def scale(spec: WorkloadSpec, work_mult: float) -> WorkloadSpec:
    """Scale a scenario's access intensity by ``work_mult``."""
    comps = _to_comps(spec)
    for c in comps:
        c["work"] *= float(work_mult)
    return with_label(_from_comps(comps),
                      f"{label_of(spec)}*{work_mult:g}")


def drift(spec: WorkloadSpec, pages_per_interval: float) -> WorkloadSpec:
    """March the whole access distribution forward by
    ``pages_per_interval`` pages per interval (mod n)."""
    comps = _to_comps(spec)
    for c in comps:
        c["drift_rate"] += float(pages_per_interval)
    return with_label(_from_comps(comps),
                      f"drift({label_of(spec)},{pages_per_interval:g})")


def pad_components(spec: WorkloadSpec, S: int) -> WorkloadSpec:
    """Extend to exactly ``S`` components with inert (never-active,
    zero-weight) filler so structurally different scenarios stack into one
    lane-batched sweep."""
    have = spec.n_components
    if have > S:
        raise ValueError(f"spec has {have} components > requested {S}")
    comps = _to_comps(spec)
    comps += [_comp(KIND_ZIPF, work=0.0, weight=0.0, t_end=0)
              for _ in range(S - have)]
    return with_label(_from_comps(comps), label_of(spec))
