"""AUA (Adaptive Unstructured Analog) workflow under EnTK (§III-B, Fig. 11).

The iterative search is *described* on the declarative API
(:mod:`repro.api`): each iteration is an :func:`~repro.api.ensemble` over
location slices, and the unknown-length iteration sequence is an
:func:`~repro.api.repeat_until` loop — which the compiler lowers onto the
exact ``post_exec``/append-listener machinery the paper describes
(iteration stages appended at runtime, never re-entering an HPC queue).
Task *results* (the computed analog values) flow between rounds through the
API's data-flow plumbing instead of hand-scraping ``stage.tasks[i].result``.

Two implementations are compared, as in Fig. 11:

* **random** — each iteration computes analogs at uniformly random new
  locations;
* **AUA** — each iteration interpolates the current estimate, measures its
  local gradient, and places new locations preferentially where the field
  changes fastest (fronts), steering the computation.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from ... import api
from ... import telemetry as tel
from ...core import AppManager, register_executable
from ...fusion import fusable, fusable_reduction
from ...rts.base import ResourceDescription
from ...rts.jax_rts import JaxRTS
from .anen import (AnEnConfig, compute_analogs, gradient_magnitude,
                   idw_empty, idw_interpolate_merge, idw_interpolate_nearest,
                   make_dataset, rmse)

_DATASETS: Dict[int, object] = {}


def _dataset(seed: int, ny: int, nx: int, n_hist: int):
    key = (seed, ny, nx, n_hist)
    if key not in _DATASETS:
        import jax
        data = make_dataset(AnEnConfig(ny=ny, nx=nx, n_hist=n_hist,
                                       seed=seed))
        if any(isinstance(leaf, jax.core.Tracer) for leaf in data):
            # first call happened inside a trace (e.g. a fused vmap of
            # analog_values without its batched impl): valid for this
            # trace, but caching would leak tracers into later calls
            return data
        _DATASETS[key] = data
    return _DATASETS[key]


def _dataset_operands(*, seed: int, ny: int, nx: int, n_hist: int,
                      k: int) -> Dict[str, object]:
    """The dataset arrays :func:`_analog_values_batched` indexes, handed to
    the fusion engine as ``operands``: a sharded round then takes them as
    replicated program inputs instead of compile-time constants (the full
    NAM-grid history is over a gigabyte)."""
    data = _dataset(seed, ny, nx, n_hist)
    return {"forecast_now": data.forecast_now,
            "hist_forecast": data.hist_forecast, "hist_obs": data.hist_obs}


def _analog_values_batched(locations, *, seed: int, ny: int, nx: int,
                           n_hist: int, k: int, forecast_now, hist_forecast,
                           hist_obs):
    """Hand-batched implementation for the fusion engine: one dispatch for
    a whole micro-batch of members.

    ``locations`` is (B, n, 2) int32 — B members' (possibly padded)
    location slices. The member axis folds into the location axis (every
    location is independent), the similarity matrix runs through the
    Pallas distance kernel, and the analog means unfold back to (B, n).
    The dataset arrives as arguments (see :func:`_dataset_operands`), so
    the function also traces under the SPMD path's ``jit(shard_map(...))``.
    The kernel runs compiled on a TPU and in interpret mode on any other
    backend.
    """
    import jax
    import jax.numpy as jnp
    from ...kernels.anen_distance import anen_distance

    b, n, _ = locations.shape
    flat = locations.reshape(b * n, 2)
    ys, xs = flat[:, 0], flat[:, 1]
    f_now = forecast_now[:, ys, xs]                 # (V, B·n)
    f_h = hist_forecast[:, :, ys, xs]               # (H, V, B·n)
    o_h = hist_obs[:, ys, xs]                       # (H, B·n)
    d2 = anen_distance(f_h, f_now,
                       interpret=jax.default_backend() != "tpu")
    _, idx = jax.lax.top_k(-d2.T, k)                # (B·n, k) most similar
    picked = jnp.take_along_axis(o_h.T, idx, axis=1)
    return picked.mean(axis=1).reshape(b, n)


@fusable(static_argnames=("seed", "ny", "nx", "n_hist", "k"),
         pad_argnames=("locations",), batched=_analog_values_batched,
         operands=_dataset_operands)
def analog_values(locations: List[List[int]], seed: int = 0, ny: int = 48,
                  nx: int = 48, n_hist: int = 120, k: int = 12):
    """EnTK task: analog predictions at a slice of locations — the fused
    AnEn member kernel. Scalar execution (LocalRTS, or a group below the
    fusion threshold) computes exactly the same values through
    :func:`compute_analogs`; fused execution batches congruent members into
    one dispatch with the Pallas distance kernel."""
    import jax.numpy as jnp
    data = _dataset(seed, ny, nx, n_hist)
    locs = jnp.asarray(locations, jnp.int32)
    return compute_analogs(data, locs, k)


register_executable("analog_values", analog_values)


@fusable(static_argnames=("lo", "hi"), pad_argnames=("values",))
def analog_refine(values, lo: float = 0.0, hi: float = 1.0):
    """Second chain link of each round: bound the analog estimates to the
    historical observation range.

    Analog means are averages of observed values, so the clip is exactly
    the identity on well-formed inputs — it is a guard against corrupted
    history windows, and (deliberately) keeps the fused/chained rounds
    bit-identical to the scalar path. What it buys structurally: every
    AnEn round is now a 2-link elementwise chain (``analog_values →
    analog_refine``), so a chain-capable RTS runs the whole round's
    micro-batches as composed dispatches with the raw analog values never
    leaving the device between the links.
    """
    import jax.numpy as jnp
    return jnp.clip(jnp.asarray(values, jnp.float32), lo, hi)


register_executable("analog_refine", analog_refine)


@fusable_reduction(kind="max")
def round_spread(values) -> float:
    """Round fan-in: the largest analog estimate of the round — a cheap
    convergence statistic (the adaptive criterion watches the estimate's
    dynamic range tighten as fronts get resolved).

    ``kind="max"`` makes the whole round a fusable DAG
    (``analog_values → analog_refine → max``): a DAG-capable RTS runs one
    composed dispatch per round, with the reduction executing device-side
    over the refined member values (``psum``-free — max is also safe over
    the engine's edge-replicated pad rows). Scalar execution keeps the
    plain ``np.max`` body bit-for-bit.
    """
    return float(np.max([np.max(np.asarray(v)) for v in values]))


register_executable("round_spread", round_spread)


class _RoundNode(api.Node):
    """What :meth:`_SearchState.make_round` returns: the refine ensemble's
    member futures PLUS the round's spread reduction. The loop's check
    stage collects all of them (``absorb`` zips results against the round's
    location slices, so the trailing spread value is simply extra), while
    the gather's presence is what turns the round into a fusable DAG."""

    def __init__(self, refine: api.Ensemble, spread) -> None:
        self.refine = refine
        self.spread = spread

    def futures(self):
        return list(self.refine.futures()) + list(self.spread.futures())


class _SearchState:
    """Shared state the adaptive post_exec hooks steer."""

    def __init__(self, method: str, seed: int, cfg: AnEnConfig,
                 per_iter: int, max_iters: int, n_tasks: int,
                 fuse: bool = True) -> None:
        self.method = method
        self.seed = seed
        self.cfg = cfg
        self.per_iter = per_iter
        self.max_iters = max_iters
        self.n_tasks = n_tasks
        self.fuse = fuse
        self.rng = np.random.default_rng(seed + (0 if method == "aua"
                                                 else 10_000))
        self.locations: List[List[int]] = []
        self.values: List[float] = []
        self.errors: List[float] = []
        self.iteration = 0
        self.data = _dataset(seed, cfg.ny, cfg.nx, cfg.n_hist)
        # bounds for the refine link (the historical observation range):
        # plain floats, so they ride the chain as static arguments
        obs = np.asarray(self.data.hist_obs)
        self.obs_lo = float(obs.min())
        self.obs_hi = float(obs.max())
        # the location slices of the round in flight: member results come
        # back as bare value arrays (device-resident on the fused path), so
        # the builder keeps the location bookkeeping host-side
        self._round_slices: List[List[List[int]]] = []
        # what absorb's IDW made of the samples: (number of samples covered,
        # the estimate, each cell's squared distance to its nearest sample),
        # device arrays the AUA proposal reads back instead of redoing them
        self._idw = None
        # each cell's 8 nearest of those samples (squared distances and
        # values, device arrays), into which absorb merges the next round
        self._kept = None

    # ---- location proposal ------------------------------------------------ #

    def initial_locations(self) -> np.ndarray:
        return self._random_new(self.per_iter)

    def _random_new(self, n: int) -> np.ndarray:
        taken = set(map(tuple, self.locations))
        out = []
        while len(out) < n:
            y = int(self.rng.integers(0, self.cfg.ny))
            x = int(self.rng.integers(0, self.cfg.nx))
            if (y, x) not in taken:
                taken.add((y, x))
                out.append([y, x])
        return np.asarray(out, np.int32)

    def _adaptive_new(self, n: int) -> np.ndarray:
        """AUA refinement: greedy picks by error-indicator × spacing.

        priority(cell) = |∇ estimate| × dist²-to-nearest-sample — the
        classical adaptive-mesh criterion: refine where the field changes
        fast *and* the sampling is still coarse. Greedy selection with
        neighbourhood suppression avoids redundant clustering on the same
        front pixel. A quarter of the budget stays uniform (coverage of
        regions the current estimate cannot see yet).

        Incremental, with the picks of the whole-grid greedy: the estimate
        and the distances are absorb's where they cover the current samples
        (else interpolated here), and each pick becomes a sample for the
        next. No distance ever grows, so ``reach = ceil(sqrt(max dist²))``,
        read before the explore points are folded in, bounds every later
        change: a new sample lowers no cell outside the box of ±reach
        around it, and each fold or pick updates only that box and its
        rows' maxima. On a tie the first cell in row-major order wins (the
        first row holding the maximum, then the first column in it), as
        ``np.argmax`` on the flat grid.
        """
        n_explore = max(1, n // 4)
        n_exploit = n - n_explore
        explore = self._random_new(n_explore)
        ny, nx = self.cfg.ny, self.cfg.nx
        kept = self._idw
        reused = kept is not None and kept[0] == len(self.locations)
        est, near = kept[1:] if reused else self._interpolate()
        grad = np.asarray(gradient_magnitude(est)).astype(np.float64)
        # smear the indicator one cell so line-like fronts are 2-3 px wide
        grad = grad + 0.5 * (np.roll(grad, 1, 0) + np.roll(grad, -1, 0)
                             + np.roll(grad, 1, 1) + np.roll(grad, -1, 1))
        d2 = np.asarray(near).astype(np.float64)
        reach = int(np.ceil(np.sqrt(d2.max())))
        iy, ix = np.arange(ny), np.arange(nx)
        cells = 0

        def visit(py: int, px: int):
            """Make (py, px) a sample: lower d2 in its box."""
            nonlocal cells
            rows = slice(max(0, py - reach), py + reach + 1)
            cols = slice(max(0, px - reach), px + reach + 1)
            box = d2[rows, cols]
            np.minimum(box, (iy[rows, None] - py) ** 2
                       + (ix[None, cols] - px) ** 2, out=box)
            cells += box.size
            return rows, cols

        for py, px in explore.tolist():
            visit(py, px)
        pri = grad * d2
        row_max = pri.max(axis=1)
        picks = []
        for _ in range(n_exploit):
            py = int(np.argmax(row_max))
            px = int(np.argmax(pri[py]))
            picks.append([py, px])
            rows, cols = visit(py, px)
            pri[rows, cols] = grad[rows, cols] * d2[rows, cols]
            row_max[rows] = pri[rows].max(axis=1)
        if tel.enabled():
            # an instant event: the proposal's time stays the body's own
            tel.event("anen.propose", "anen", picks=n_exploit,
                      explore=n_explore, reach=reach, cells_updated=cells,
                      reused_estimate=int(reused))
        return np.concatenate([explore, np.asarray(picks, np.int32)],
                              axis=0)

    def _interpolate(self):
        """The IDW estimate of the samples and each cell's squared distance
        to its nearest sample, as device arrays."""
        import jax.numpy as jnp
        locs = jnp.asarray(self.locations, jnp.int32)
        vals = jnp.asarray(self.values, jnp.float32)
        return idw_interpolate_nearest(locs, vals, self.cfg.ny, self.cfg.nx)

    def propose(self, n: int) -> np.ndarray:
        if self.method == "aua" and self.iteration > 0:
            return self._adaptive_new(n)
        return self._random_new(n)

    # ---- bookkeeping ------------------------------------------------------- #

    def absorb(self, results: List) -> None:
        """Fold one round's task results (analog values) into the estimate.

        ``results`` line up with the round's location slices by member
        index; each value may be a list, ndarray, or a device-resident
        :class:`~repro.fusion.ArrayResult` — ``np.asarray`` reads them all.

        The IDW merges the round's new samples into each cell's kept 8
        nearest when those cover exactly the samples before this round;
        otherwise (the first round, or samples added behind absorb's back)
        it rebuilds them from every sample.
        """
        before = len(self.locations)
        for slice_locs, r in zip(self._round_slices, results):
            if r is None:
                continue
            self.locations.extend(slice_locs)
            self.values.extend(np.asarray(r).tolist())
        merged = self._kept is not None and self._idw[0] == before
        first = before if merged else 0
        if first < len(self.locations):
            import jax.numpy as jnp
            ny, nx = self.cfg.ny, self.cfg.nx
            kept = self._kept if merged else idw_empty(ny, nx)
            self._kept = None       # the merge takes over its buffers
            locs = jnp.asarray(self.locations[first:],
                               jnp.int32).reshape(-1, 2)
            vals = jnp.asarray(self.values[first:], jnp.float32)
            est, near, self._kept = idw_interpolate_merge(kept, locs, vals,
                                                          ny, nx)
            self._idw = (len(self.locations), est, near)
        if tel.enabled():
            tel.event("anen.absorb", "anen",
                      new_samples=len(self.locations) - before,
                      merged=int(merged),
                      columns=len(self.locations) - first)
        self.errors.append(rmse(self._idw[1], self.data.truth))
        self.iteration += 1

    # ---- declarative description ------------------------------------------- #

    def make_round(self, ctx: api.LoopContext) -> api.Node:
        """One iteration: a fusable DAG over location slices
        (``analog_values → analog_refine → max``, elementwise between the
        first two links, whole-round fan-in at the spread gather).

        ``ctx.results`` (the previous round's values) were absorbed by
        :meth:`converged` before this builder runs, so proposals always see
        the up-to-date estimate — including on journal resume, where rounds
        replay in order through the same two hooks. DAG/chain detection
        runs when the round is planned at runtime, so every adaptive round
        gets the composed-dispatch data plane — a DAG-capable RTS executes
        the whole round (both links plus the device-side reduction) as ONE
        dispatch — not just static workflows.
        """
        locs = self.propose(self.per_iter)
        slices = [sl for sl in np.array_split(locs, self.n_tasks)
                  if len(sl)]
        self._round_slices = [sl.tolist() for sl in slices]
        search = api.ensemble(
            analog_values,
            over=[{"seed": self.seed, "ny": self.cfg.ny, "nx": self.cfg.nx,
                   "n_hist": self.cfg.n_hist, "k": self.cfg.k,
                   "locations": sl.tolist()} for sl in slices],
            name=f"{self.method}-it{ctx.round}-{self.seed}",
            max_retries=1, fuse=self.fuse)
        refine = search.then(
            analog_refine,
            over=[{"lo": self.obs_lo, "hi": self.obs_hi} for _ in slices],
            name=f"{self.method}-it{ctx.round}-{self.seed}-ref",
            max_retries=1, fuse=self.fuse)
        spread = api.gather(
            refine, round_spread,
            name=f"{self.method}-it{ctx.round}-{self.seed}-spread")
        return _RoundNode(refine, spread)

    def converged(self, ctx: api.LoopContext) -> bool:
        """repeat_until predicate: absorb the finished round, then decide."""
        self.absorb(ctx.results)
        return self.iteration >= self.max_iters

    def as_loop(self) -> api.Loop:
        return api.repeat_until(
            self.converged, self.make_round,
            name=f"anen-{self.method}-{self.seed}",
            max_rounds=self.max_iters)


def _run(method: str, seed: int, *, ny: int, nx: int, n_hist: int,
         per_iter: int, max_iters: int, n_tasks: int, slots: int,
         timeout: float, fuse: bool = True, shard: bool = True,
         devices=None) -> Dict:
    """One campaign; ``devices`` pins the JaxRTS inventory (default: every
    device JAX sees). The result carries the runtime that ran it (``rts``)
    and every computed analog with its location."""
    cfg = AnEnConfig(ny=ny, nx=nx, n_hist=n_hist, seed=seed)
    search = _SearchState(method, seed, cfg, per_iter, max_iters, n_tasks,
                          fuse=fuse)
    holder: Dict[str, JaxRTS] = {}

    def make_rts() -> JaxRTS:
        # the fused path: congruent analog members of one round batch into
        # a single dispatch on the device pool (fuse=False or a LocalRTS
        # factory reproduces the per-task scalar behaviour bit-for-bit). On
        # a multi-device pool a wide round shards across the whole mesh
        # (shard=False opts out)
        holder["rts"] = JaxRTS(devices=devices, slot_oversubscribe=slots,
                               shard=shard)
        return holder["rts"]

    amgr = AppManager(resources=ResourceDescription(slots=slots),
                      rts_factory=make_rts, heartbeat_interval=1.0)
    compiled = api.compile(search.as_loop(), name=f"anen-{method}-{seed}")
    amgr.workflow = compiled
    amgr.run(timeout=timeout)
    if compiled.hook_errors:
        raise RuntimeError(f"anen adaptive hooks failed: "
                           f"{compiled.hook_errors}")
    # everything we report lives in the search state; release the store
    # namespace so repeated runs (compare_methods sweeps) stay bounded
    compiled.close()
    return {"method": method, "seed": seed,
            "n_locations": len(search.locations),
            "rounds": search.iteration,
            "errors": search.errors, "final_rmse": search.errors[-1],
            "all_done": amgr.all_done,
            "locations": search.locations, "values": search.values,
            "rts": holder.get("rts")}


def run_adaptive(seed: int = 0, **kw) -> Dict:
    return _run("aua", seed, **_defaults(kw))


def run_random(seed: int = 0, **kw) -> Dict:
    return _run("random", seed, **_defaults(kw))


def _defaults(kw: Dict) -> Dict:
    out = dict(ny=48, nx=48, n_hist=120, per_iter=60, max_iters=5,
               n_tasks=4, slots=4, timeout=600.0)
    out.update(kw)
    return out


def compare_methods(repeats: int = 5, **kw) -> Dict:
    """Fig.-11 comparison: error distributions over repeated runs."""
    aua, rnd = [], []
    for r in range(repeats):
        aua.append(run_adaptive(seed=r, **kw)["final_rmse"])
        rnd.append(run_random(seed=r, **kw)["final_rmse"])
    return {
        "repeats": repeats,
        "aua_rmse": aua,
        "random_rmse": rnd,
        "aua_median": float(np.median(aua)),
        "random_median": float(np.median(rnd)),
        "aua_wins": int(sum(a < b for a, b in zip(aua, rnd))),
    }
