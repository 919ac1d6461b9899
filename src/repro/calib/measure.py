"""Measurement runner — the paper's §4 stressor×victim sweep as data.

The calibration loop starts here: colocate each victim kernel with a
calibrated single-axis stressor at intensity λ (and with cache-polluter
probes of growing working set), record the victim's observed slowdown,
and hand the resulting ``MeasurementSet`` to the fitter
(``repro.calib.fit``).  The sweep itself is backend-pluggable:

  * ``SyntheticBackend`` — serves slowdowns from HIDDEN ground-truth
    ``KernelProfile``s through the water-filling estimator (optionally
    noised under a seeded ``numpy.random.Generator``).  The whole
    measure→fit→validate pipeline runs in CI without hardware, and the
    hidden truths make round-trip recovery a *checkable* property
    (``benchmarks/bench_calib.py``).
  * ``PallasBackend`` — runs the Pallas stressor kernels
    (``repro.kernels.stressors``, compiled to Mosaic unless the caller
    asks for interpret mode) concurrently with real victim callables
    and times the victim with the shared median+IQR repeat
    timer (``median_iqr_time`` — also used by
    ``benchmarks/tpu_native.py``).

A ``Colocation`` names its background *declaratively* — stressor
``(axis, intensity, working_set)`` specs plus cohort victims by name —
so the fitter and validator can rebuild the exact same background from
analytic stressor profiles without ever seeing the hidden truths.
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from repro.core.estimator import solve_scenarios
from repro.core.profile import KernelProfile
from repro.core.resources import RESOURCE_AXES, DeviceModel
from repro.core.scenario import Scenario
from repro.core.sensitivity import stressor

# the default §4 grids: fit on these λ / working-set points, validate on
# points BETWEEN them (see repro.calib.validate.HOLDOUT_LAMBDAS)
FIT_LAMBDAS: Tuple[float, ...] = (0.1, 0.25, 0.5, 0.75, 0.9)
CACHE_WS_FRACTIONS: Tuple[float, ...] = (0.25, 0.5, 1.0, 2.0)
CACHE_PROBE_INTENSITY = 0.5          # hbm intensity of the polluter probes
# reverse-probe intensities: stressor at λ observed against the measured
# kernel — its slowdown λ/(1−u) resolves victim demands u > 1−λ that
# max-min hides from victim-side probes (u below fair share)
REVERSE_LAMBDAS: Tuple[float, ...] = (0.5, 0.75, 0.9, 0.98)


# ------------------------------------------------------------------ #
#  The shared repeat timer (median + IQR)                              #
# ------------------------------------------------------------------ #
def median_iqr_time(fn: Callable[[], object], repeats: int = 5,
                    warmup: int = 1) -> Tuple[float, float]:
    """Time ``fn`` (blocking on its jax result) ``repeats`` times after
    ``warmup`` untimed calls; return ``(median_s, iqr_s)``.  The one
    timer for every wall-clock kernel measurement — the tpu_native
    stressor suite and the calib Pallas backend both use it, so a
    timing-methodology change lands in one place."""
    import jax

    for _ in range(max(warmup, 0)):
        jax.block_until_ready(fn())
    ts = np.empty(max(repeats, 1), np.float64)
    for i in range(len(ts)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts[i] = time.perf_counter() - t0
    return (float(np.median(ts)),
            float(np.percentile(ts, 75) - np.percentile(ts, 25)))


# ------------------------------------------------------------------ #
#  The measurement vocabulary                                          #
# ------------------------------------------------------------------ #
@dataclass(frozen=True)
class StressorSpec:
    """One calibrated stressor: ``intensity`` of ``axis`` capacity (plus
    an optional cache working set for polluter probes).  Maps 1:1 to
    ``repro.core.sensitivity.stressor`` and to the Pallas kernels."""
    axis: str
    intensity: float
    working_set: float = 0.0

    def profile(self, dev: DeviceModel) -> KernelProfile:
        return stressor(self.axis, self.intensity, dev,
                        working_set=self.working_set)


@dataclass(frozen=True)
class Colocation:
    """One colocated run: ``victim`` (by name) next to analytic
    stressors and/or other measured kernels (``cohort``, by name).

    ``observe`` selects which side's slowdown the run records:
    ``"victim"`` (default) times the measured kernel; ``"stressor"``
    times the FIRST stressor while the measured kernel contends as
    background.  Reverse probes are essential, not a nicety: under
    max-min sharing a kernel whose demand sits below the fair share is
    never throttled itself, so victim-side probes carry zero signal
    about it — but the known stressor's slowdown reveals exactly how
    much of the axis the kernel takes away (§4 measures both sides).
    """
    victim: str
    stressors: Tuple[StressorSpec, ...] = ()
    cohort: Tuple[str, ...] = ()
    observe: str = "victim"

    @property
    def single_axis(self) -> Optional[str]:
        """The axis of a pure single-stressor probe (else None)."""
        if len(self.stressors) == 1 and not self.cohort \
                and self.observe == "victim" \
                and self.stressors[0].working_set == 0.0:
            return self.stressors[0].axis
        return None

    @property
    def is_cache_probe(self) -> bool:
        return any(s.working_set > 0.0 for s in self.stressors)


@dataclass
class MeasurementSet:
    """The sweep's output: observations + per-victim isolated times,
    everything the fitter needs (and nothing the backend should hide)."""
    device: DeviceModel
    colocations: List[Colocation]
    slowdowns: np.ndarray                # (n,) observed victim slowdowns
    isolated_times: Dict[str, float]     # victim -> measured t_iso (s)

    def __len__(self) -> int:
        return len(self.colocations)

    def of_victim(self, name: str) -> Tuple[List[Colocation], np.ndarray]:
        idx = [i for i, c in enumerate(self.colocations) if c.victim == name]
        return [self.colocations[i] for i in idx], self.slowdowns[idx]

    @property
    def victims(self) -> List[str]:
        return sorted(self.isolated_times)


def colocation_scenario(c: Colocation, victim_profile: KernelProfile,
                        dev: DeviceModel,
                        cohort: Mapping[str, KernelProfile]) -> Scenario:
    """Lower a Colocation to the estimator query whose first victim row
    is the OBSERVED kernel — the measured kernel itself, or (reverse
    probes) the first stressor with the measured kernel as background.
    The one lowering both backends and the fitter share, so a fitted
    candidate is scored under exactly the semantics it was measured."""
    stress = tuple(s.profile(dev) for s in c.stressors)
    others = tuple(cohort[n] for n in c.cohort)
    if c.observe == "stressor":
        if not stress:
            raise ValueError("observe='stressor' needs a stressor")
        return Scenario((stress[0],),
                        stress[1:] + (victim_profile,) + others)
    return Scenario((victim_profile,), stress + others)


def sweep_colocations(victims: Sequence[str], dev: DeviceModel,
                      axes: Sequence[str] = RESOURCE_AXES,
                      lambdas: Sequence[float] = FIT_LAMBDAS,
                      cache_ws_fractions: Sequence[float] = CACHE_WS_FRACTIONS
                      ) -> List[Colocation]:
    """The §4 calibration sweep: every victim × every axis × every λ as
    single-stressor probes, same-axis multi-stressor probes (under
    max-min sharing a single stressor can't throttle a victim below the
    1/2 fair share — k saturating stressors lower the victim's share to
    1/(k+1), exposing demands down there), plus hbm polluter probes with
    working sets swept around the device cache capacity (the Fig. 3
    cliff — what identifies ``cache_working_set``/``cache_hit_fraction``)."""
    out: List[Colocation] = []
    for v in victims:
        for axis in axes:
            for lam in lambdas:
                out.append(Colocation(v, (StressorSpec(axis, lam),)))
            for k in (2, 3):
                out.append(Colocation(
                    v, tuple(StressorSpec(axis, 0.9) for _ in range(k))))
            for lam in REVERSE_LAMBDAS:
                out.append(Colocation(v, (StressorSpec(axis, lam),),
                                      observe="stressor"))
        for f in cache_ws_fractions:
            out.append(Colocation(v, (StressorSpec(
                "hbm", CACHE_PROBE_INTENSITY,
                working_set=f * dev.cache_capacity),)))
    return out


# ------------------------------------------------------------------ #
#  Synthetic backend: hidden truth through the estimator               #
# ------------------------------------------------------------------ #
class SyntheticBackend:
    """Serve measurements from hidden ground-truth profiles.

    The backend is the only holder of ``truth``; consumers see nothing
    but observed slowdowns and isolated times — exactly the information
    a hardware run would yield.  With ``noise > 0`` every observation is
    multiplied by ``exp(noise * N(0, 1))`` drawn from a Generator seeded
    at construction, so repeated identical call sequences stay
    bit-identical per seed.
    """

    def __init__(self, truth: Mapping[str, KernelProfile],
                 dev: DeviceModel, noise: float = 0.0, seed: int = 0):
        self._truth = dict(truth)
        self.device = dev
        self.noise = float(noise)
        self._rng = np.random.default_rng(seed)

    def isolated_time(self, victim: str) -> float:
        return float(self._truth[victim].isolated_time(self.device))

    def measure(self, colocations: Sequence[Colocation]) -> np.ndarray:
        """Observed victim slowdowns, one per colocation, in order —
        ONE batched estimator solve over the hidden truths."""
        colocations = list(colocations)
        if not colocations:
            return np.zeros(0, np.float64)
        scenarios = [colocation_scenario(c, self._truth[c.victim],
                                         self.device, self._truth)
                     for c in colocations]
        slows = solve_scenarios(scenarios, self.device).slowdowns[:, 0]
        slows = np.asarray(slows, np.float64).copy()
        if self.noise > 0:
            slows *= np.exp(self.noise
                            * self._rng.standard_normal(len(slows)))
        return slows

    def run_sweep(self, victims: Sequence[str],
                  axes: Sequence[str] = RESOURCE_AXES,
                  lambdas: Sequence[float] = FIT_LAMBDAS,
                  cache_ws_fractions: Sequence[float] = CACHE_WS_FRACTIONS
                  ) -> MeasurementSet:
        cols = sweep_colocations(victims, self.device, axes, lambdas,
                                 cache_ws_fractions)
        return MeasurementSet(
            self.device, cols, self.measure(cols),
            {v: self.isolated_time(v) for v in victims})


# ------------------------------------------------------------------ #
#  Pallas backend: real colocated kernel runs                          #
# ------------------------------------------------------------------ #
# Per-axis stressor kernels (repro.kernels.stressors).  Intensity scales
# the work per dispatch; on real hardware the loop thread keeps the axis
# busy for the victim's whole run.  Absolute intensity calibration
# (λ of peak) needs TPU time — see ROADMAP A6.
_STRESSOR_TILE = 128

StressorKernel = Tuple[Callable, Callable, Tuple[Tuple[Tuple[int, ...], object], ...]]


def stressor_kernel(spec: StressorSpec,
                    interpret: bool = False) -> StressorKernel:
    """The Pallas call that stresses ``spec.axis`` at ``spec.intensity``,
    as ``(kernel, reference, operands)``: the kernel and its jnp oracle
    (``repro.kernels.ref``) take the same arrays, whose ``(shape,
    dtype)`` pairs are ``operands``.  The calibration sweep, the chip
    smoke run and the TPU compile tests all build stressors here."""
    from functools import partial

    import jax.numpy as jnp

    from repro.kernels import ref, stressors

    lam = max(min(spec.intensity, 1.0), 0.05)
    T = _STRESSOR_TILE
    if spec.axis == "mxu":
        iters = max(1, int(round(32 * lam)))
        return (partial(stressors.stress_mxu, iters=iters,
                        interpret=interpret),
                partial(ref.ref_stress_mxu, iters=iters),
                (((2, T, T), jnp.float32), ((T, T), jnp.float32)))
    if spec.axis in ("vpu", "issue"):
        iters = max(1, int(round(64 * lam)))
        return (partial(stressors.stress_vpu, iters=iters, ilp=4,
                        interpret=interpret),
                partial(ref.ref_stress_vpu, iters=iters, ilp=4),
                (((256, T), jnp.float32),))
    if spec.axis in ("hbm", "l2", "ici"):
        ws = spec.working_set or 8 * (1 << 20)
        rows = max(8, int(ws / (4 * T)))
        rows = 8 * max(1, round(rows / 8 * lam))
        return (partial(stressors.stress_hbm, interpret=interpret),
                ref.ref_stress_hbm,
                (((rows, T), jnp.float32),))
    if spec.axis == "smem":
        iters = max(1, int(round(32 * lam)))
        return (partial(stressors.stress_vmem, iters=iters, stride=8,
                        interpret=interpret),
                partial(ref.ref_stress_vmem, iters=iters, stride=8),
                (((512, T), jnp.float32),))
    raise ValueError(f"no Pallas stressor for axis {spec.axis!r}")


def stressor_operands(operands, seed: int = 17) -> List[object]:
    """Seeded standard-normal arrays for a stressor's ``operands``."""
    import jax

    keys = jax.random.split(jax.random.PRNGKey(seed), len(operands))
    return [jax.random.normal(k, shape, dtype)
            for k, (shape, dtype) in zip(keys, operands)]


def _stressor_call(spec: StressorSpec, interpret: bool) -> Callable[[], object]:
    kernel, _, operands = stressor_kernel(spec, interpret)
    args = stressor_operands(operands)
    return lambda: kernel(*args)


class PallasBackend:
    """Measure real colocated runs: victim callables timed (median of N
    repeats — the shared ``median_iqr_time``) while stressor kernels
    loop on background threads.

    ``victims`` maps a name to a zero-arg callable issuing the victim
    kernel (returning a jax value to block on).  The stressors compile
    to Mosaic; ``interpret=True`` (the CPU tests) runs them in the
    Pallas interpreter instead, where "colocation" is thread-level
    concurrency — enough to smoke-test the pipeline end to end.  Whether
    threads of one process contend on one TPU core or run one after
    another is ROADMAP A3.  Wall-clock based, hence NOT deterministic —
    CI gates use ``SyntheticBackend``.
    """

    def __init__(self, victims: Mapping[str, Callable[[], object]],
                 dev: DeviceModel, repeats: int = 5,
                 interpret: bool = False):
        self._victims = dict(victims)
        self.device = dev
        self.repeats = int(repeats)
        self.interpret = interpret
        self._iso: Dict[str, float] = {}

    def isolated_time(self, victim: str) -> float:
        t = self._iso.get(victim)
        if t is None:
            t, _ = median_iqr_time(self._victims[victim],
                                   repeats=self.repeats)
            self._iso[victim] = t
        return t

    def _stressor_iso(self, spec: StressorSpec) -> float:
        t = self._iso.get(repr(spec))
        if t is None:
            t, _ = median_iqr_time(_stressor_call(spec, self.interpret),
                                   repeats=self.repeats)
            self._iso[repr(spec)] = t
        return t

    def _timed_colocation(self, timed: Callable[[], object],
                          background: Sequence[Callable[[], object]]
                          ) -> float:
        import threading

        import jax

        stop = threading.Event()

        def spin(fn):
            while not stop.is_set():
                jax.block_until_ready(fn())

        threads = [threading.Thread(target=spin, args=(fn,), daemon=True)
                   for fn in background]
        for th in threads:
            th.start()
        try:
            t, _ = median_iqr_time(timed, repeats=self.repeats)
        finally:
            stop.set()
            for th in threads:
                th.join()
        return t

    def measure(self, colocations: Sequence[Colocation]) -> np.ndarray:
        out = np.empty(len(colocations), np.float64)
        for i, c in enumerate(colocations):
            if c.cohort:
                raise NotImplementedError(
                    "PallasBackend measures stressor backgrounds; "
                    "victim-cohort mixes need per-victim callables "
                    "running concurrently (real-TPU work, ROADMAP 4)")
            fns = [_stressor_call(s, self.interpret) for s in c.stressors]
            if c.observe == "stressor":
                iso = self._stressor_iso(c.stressors[0])
                col = self._timed_colocation(
                    fns[0], fns[1:] + [self._victims[c.victim]])
            else:
                iso = self.isolated_time(c.victim)
                col = self._timed_colocation(self._victims[c.victim], fns)
            out[i] = max(col / max(iso, 1e-12), 1.0)
        return out

    def run_sweep(self, victims: Sequence[str],
                  axes: Sequence[str] = RESOURCE_AXES,
                  lambdas: Sequence[float] = FIT_LAMBDAS,
                  cache_ws_fractions: Sequence[float] = ()
                  ) -> MeasurementSet:
        cols = sweep_colocations(list(victims), self.device, axes, lambdas,
                                 cache_ws_fractions)
        return MeasurementSet(
            self.device, cols, self.measure(cols),
            {v: self.isolated_time(v) for v in victims})
