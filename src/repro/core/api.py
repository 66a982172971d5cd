"""Unified planner API: a capability-aware scheme registry behind one
``plan()`` / ``plan_many()`` entry point.

The paper contributes a *family* of regeneration planners — star, FR, TR,
FTR, plus the Shah [6] and RCTREE [7] baselines — evaluated under one
harness, and new schemes keep landing.  Historically that family was wired
through three hand-synchronized dispatch tables (``core.SCHEMES``,
``core.batched.BATCHED_SCHEMES``, ``storage.simulator._WITNESS_SCHEMES``)
and every caller re-implemented its own engine selection and scalar-
fallback logic.  This module replaces all of that with a single registry:

* Each scheme is one :class:`SchemeSpec` declaring its capabilities —
  the scalar planner, the batched planner (or ``None``), whether the
  planners accept the ``witness=`` engine selector, and whether the scheme
  produces trees or stars.  Registration is one :func:`register_scheme`
  call (usable as a decorator), so the next scheme — e.g. the
  topology-aware selection of arXiv:1506.05579 — is a single-file plug-in.
* :func:`plan` plans one network, :func:`plan_many` a whole batch.  Both
  own engine resolution (``engine="auto" | "scalar" | "batched" | "jax"``),
  kwarg forwarding (``witness=`` reaches exactly the schemes that declared
  it), and the fallback chain for schemes without the requested engine —
  declared by the registry and announced by one RuntimeWarning per scheme
  per process when the missing engine was explicitly requested.

Engine resolution.  ``"auto"`` picks the cheapest correct engine for the
call shape: the scalar planner for a single network, the batched planner
(when registered) for a batch — falling back to the scalar loop *silently*
for schemes that declared ``batched=None``.  ``"batched"`` insists on the
vectorized engine and warns once per scheme when it has to fall back;
``"scalar"`` always runs the per-network oracle planners.  ``"jax"``
routes through the jit-compiled :mod:`repro.core.jax_engine` tier for the
schemes that declared one (star/fr/tr/ftr when jax is importable) and
falls back batched-then-scalar, warning once per scheme, otherwise.
``"auto"`` never resolves to jax: the NumPy planners stay the default
(and the golden-file oracle) on CPU; the jax tier is opt-in.

Ragged batches.  ``plan_many`` also accepts a *mixed fan-out* batch — a
sequence of overlays whose ``d`` differ (real repair events see whatever
helpers survive).  Overlays are bucketed by ``d``, each bucket planned in
one engine call against ``dataclasses.replace(params, d=...)``, and the
results reassembled in input order, padded to the widest ``d`` (see
:func:`plan_many`).

``SCHEMES`` / ``BATCHED_SCHEMES`` / ``plan_batch`` remain importable from
``repro.core`` as thin deprecation shims over the registry (one
DeprecationWarning per name per process) so external code keeps working.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import warnings
from typing import (Callable, Dict, Iterator, List, Mapping, Optional,
                    Sequence, Tuple, Union)

import numpy as np

from repro.obs.spans import span

from .params import CodeParams, OverlayNetwork, RepairPlan
from .star import plan_fr, plan_shah, plan_star
from .tree import plan_tr
from .ftr import plan_ftr
from .rctree import plan_rctree
from .batched import (BatchPlanResult, _pstage, caps_tensor, plan_fr_batch,
                      plan_ftr_batch, plan_shah_batch, plan_star_batch,
                      plan_tr_batch, plans_from_batch)

__all__ = [
    "BATCHED_SCHEMES", "SCHEMES", "SchemeSpec", "get_scheme", "plan",
    "plan_many", "register_scheme", "scheme_names", "schemes",
    "unregister_scheme",
]

ScalarPlanner = Callable[..., RepairPlan]
BatchedPlanner = Callable[..., BatchPlanResult]
ENGINES = ("auto", "scalar", "batched", "jax")
TOPOLOGIES = ("star", "tree")

HAS_JAX = importlib.util.find_spec("jax") is not None


def _lazy_jax(attr: str) -> Optional[BatchedPlanner]:
    """Deferred binding of a ``repro.core.jax_engine`` planner.

    Importing jax (and tracing/compiling kernels) costs seconds; the
    registry must stay cheap to import for the scalar/batched-only
    callers, so the jax module is imported on *first call*, not at
    registration.  Returns None when jax itself is absent from the
    environment — the spec then declares ``jax=None`` and the dispatcher
    falls back exactly as for any other missing engine.
    """
    if not HAS_JAX:
        return None

    def _call(caps, params, **kw):
        from . import jax_engine
        return getattr(jax_engine, attr)(caps, params, **kw)

    _call.__name__ = attr
    _call.__qualname__ = f"jax_engine.{attr}"
    return _call


@dataclasses.dataclass(frozen=True)
class SchemeSpec:
    """One registered regeneration scheme and its declared capabilities.

    ``scalar`` is the per-network oracle planner ``(net, params, **kw) ->
    RepairPlan``; ``batched`` the vectorized planner ``(caps, params, **kw)
    -> BatchPlanResult`` or ``None`` when the scheme has not been
    vectorized (the dispatcher then runs the declared scalar fallback).
    ``jax`` is the jit-compiled planner with the same batched signature,
    or ``None`` when the scheme has no JAX port (or jax is not importable
    in this environment) — the dispatcher then falls back batched-first.
    ``accepts_witness`` marks planners taking the ``witness=`` selector for
    the traffic-minimal witness engine (exact level cut vs scipy LP);
    ``accepts_profile`` marks *batched* planners taking the ``profile=``
    hook (ISSUE 7: per-stage wall-time instrumentation, the
    ``repro.obs.profile.PlannerProfile`` contract); ``topology`` is
    ``"tree"`` for schemes that search regeneration trees and ``"star"``
    for direct-to-newcomer schemes.
    """

    name: str
    scalar: ScalarPlanner
    batched: Optional[BatchedPlanner] = None
    jax: Optional[BatchedPlanner] = None
    accepts_witness: bool = False
    accepts_profile: bool = False
    topology: str = "star"
    description: str = ""

    def __post_init__(self):
        if self.topology not in TOPOLOGIES:
            raise ValueError(f"topology must be one of {TOPOLOGIES}, "
                             f"got {self.topology!r}")

    @property
    def produces_tree(self) -> bool:
        return self.topology == "tree"


_REGISTRY: Dict[str, SchemeSpec] = {}


def register_scheme(name: str, scalar: Optional[ScalarPlanner] = None, *,
                    batched: Optional[BatchedPlanner] = None,
                    jax: Optional[BatchedPlanner] = None,
                    accepts_witness: bool = False,
                    accepts_profile: bool = False, topology: str = "star",
                    description: str = "", replace: bool = False):
    """Register a scheme; usable directly or as a decorator.

    Direct form (returns the :class:`SchemeSpec`)::

        register_scheme("fr", plan_fr, batched=plan_fr_batch,
                        accepts_witness=True)

    Decorator form (returns the planner unchanged)::

        @register_scheme("topo", batched=plan_topo_batch, topology="tree")
        def plan_topo(net, params): ...

    ``replace=True`` allows overwriting an existing entry (tests, plugin
    reload); otherwise double registration raises ValueError.
    """
    def _register(fn: ScalarPlanner) -> SchemeSpec:
        if name in _REGISTRY and not replace:
            raise ValueError(f"scheme {name!r} is already registered; "
                             f"pass replace=True to overwrite")
        spec = SchemeSpec(name=name, scalar=fn, batched=batched, jax=jax,
                          accepts_witness=accepts_witness,
                          accepts_profile=accepts_profile,
                          topology=topology, description=description)
        _REGISTRY[name] = spec
        return spec

    if scalar is None:
        def _decorator(fn: ScalarPlanner) -> ScalarPlanner:
            _register(fn)
            return fn
        return _decorator
    return _register(scalar)


def unregister_scheme(name: str) -> None:
    """Remove a scheme from the registry (tests / plugin teardown)."""
    _REGISTRY.pop(get_scheme(name).name)


def get_scheme(name: str) -> SchemeSpec:
    """Resolve a scheme name, with an error that lists what is registered."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown scheme {name!r}; registered schemes: "
                         f"{sorted(_REGISTRY)}") from None


def schemes() -> Tuple[SchemeSpec, ...]:
    """All registered specs, in registration order."""
    return tuple(_REGISTRY.values())


def scheme_names(batched: Optional[bool] = None,
                 topology: Optional[str] = None,
                 jax: Optional[bool] = None) -> Tuple[str, ...]:
    """Registered scheme names in registration order, optionally filtered
    by capability: ``batched=True`` keeps schemes with a vectorized
    planner, ``batched=False`` the declared scalar-only ones; ``jax=True``
    keeps schemes with a jit-compiled planner *available in this
    environment* (always empty when jax is not importable);
    ``topology="star"|"tree"`` filters by produced structure."""
    out = []
    for spec in _REGISTRY.values():
        if batched is not None and (spec.batched is not None) != batched:
            continue
        if jax is not None and (spec.jax is not None) != jax:
            continue
        if topology is not None and spec.topology != topology:
            continue
        out.append(spec.name)
    return tuple(out)


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------

_warned_scalar_fallback: set = set()
_warned_jax_fallback: set = set()


def _warn_scalar_fallback(scheme: str, entry: str) -> None:
    """One warning per scheme per process — not one per call — when the
    batched engine was requested for a scheme registered without one."""
    if scheme not in _warned_scalar_fallback:
        _warned_scalar_fallback.add(scheme)
        warnings.warn(
            f"{entry}(engine='batched'): no batched planner registered for "
            f"{scheme!r} (the registry declares batched=None); falling back "
            f"to the scalar planner for all networks", RuntimeWarning,
            stacklevel=4)


def _warn_jax_fallback(scheme: str, entry: str, fallback: str) -> None:
    """One warning per scheme per process when the jax engine was requested
    for a scheme without a JAX port (or with jax absent from the env)."""
    if scheme not in _warned_jax_fallback:
        _warned_jax_fallback.add(scheme)
        why = ("the scheme declares no JAX planner" if HAS_JAX
               else "jax is not importable in this environment")
        warnings.warn(
            f"{entry}(engine='jax'): no JAX planner available for "
            f"{scheme!r} ({why}); falling back to the {fallback} engine",
            RuntimeWarning, stacklevel=4)


def _resolve_engine(spec: SchemeSpec, engine: str, entry: str) -> str:
    """Map a requested engine onto what the registry can actually run.

    ``"auto"`` never resolves to jax — the NumPy planners are the oracle
    and the CPU default; the jit tier is opt-in per call.  Explicit
    requests that cannot be honored warn once per scheme and degrade along
    jax -> batched -> scalar.
    """
    if engine == "jax":
        if spec.jax is not None:
            return "jax"
        fallback = "batched" if spec.batched is not None else "scalar"
        _warn_jax_fallback(spec.name, entry, fallback)
        return fallback
    if engine == "batched" and spec.batched is None:
        _warn_scalar_fallback(spec.name, entry)
        return "scalar"
    if engine == "auto":
        return "batched" if spec.batched is not None else "scalar"
    return engine


def _planner_kwargs(spec: SchemeSpec, witness: str, kwargs: dict) -> dict:
    """Forward ``witness`` to exactly the schemes that declared it; other
    kwargs pass through verbatim (the planner rejects what it can't take)."""
    kw = dict(kwargs)
    if spec.accepts_witness:
        kw["witness"] = witness
    return kw


def _check_engine(engine: str) -> None:
    if engine not in ENGINES:
        raise ValueError(f"unknown engine {engine!r}; expected one of "
                         f"{ENGINES}")


def plan(net: OverlayNetwork, params: CodeParams, scheme: str,
         engine: str = "auto", witness: str = "exact",
         profile=None, **kwargs) -> RepairPlan:
    """Plan one regeneration of ``net`` with ``scheme``.

    ``engine="auto"`` (default) runs the scalar planner — the correctness
    oracle, and the cheapest engine for a single network.  ``"batched"``
    and ``"jax"`` route through the vectorized planners as a B=1 batch
    (falling back along jax -> batched -> scalar, with a once-per-scheme
    RuntimeWarning, when the registry declares no such engine for the
    scheme).  ``witness`` selects the traffic-minimal
    witness engine and reaches exactly the schemes that declared
    ``accepts_witness``; ``profile`` (optional, a
    ``repro.obs.profile.PlannerProfile``-shaped object) records the call
    shape and wall time — planners that declared ``accepts_profile``
    additionally time their internal stages; extra ``**kwargs`` (e.g.
    ``beta_max=`` for shah, ``region=`` for fr/ftr) are forwarded
    verbatim.  Profiling never changes what is planned.
    """
    _check_engine(engine)
    spec = get_scheme(scheme)
    kw = _planner_kwargs(spec, witness, kwargs)
    resolved = "scalar" if engine == "auto" else \
        _resolve_engine(spec, engine, "plan")
    if profile is not None:
        profile.note(scheme=spec.name, batch=1, engine=resolved)
    if resolved in ("batched", "jax"):
        planner = spec.batched if resolved == "batched" else spec.jax
        if resolved == "batched" and spec.accepts_profile \
                and profile is not None:
            kw["profile"] = profile
        with _pstage(profile, "total"):
            res = planner(caps_tensor([net]), params, **kw)
        return plans_from_batch(res, params)[0]
    with _pstage(profile, "total"):
        return spec.scalar(net, params, **kw)


def plan_many(nets: Union[np.ndarray, Sequence[OverlayNetwork]],
              params: CodeParams, scheme: str, engine: str = "auto",
              witness: str = "exact", profile=None,
              **kwargs) -> BatchPlanResult:
    """Plan one scheme across a batch of networks.

    ``nets`` is either a ``(B, d+1, d+1)`` capacity tensor (see
    :func:`repro.core.caps_tensor`) or a sequence of
    :class:`OverlayNetwork`.  ``engine="auto"`` (default) uses the batched
    planner when the registry has one and the scalar loop otherwise —
    silently, because the fallback is *declared*; ``engine="batched"``
    additionally warns once per scheme when it has to fall back;
    ``engine="jax"`` routes through the jit-compiled tier for schemes that
    declared one and falls back batched-then-scalar (once-per-scheme
    RuntimeWarning); ``engine="scalar"`` always runs the per-network
    oracle.  ``"auto"`` never resolves to jax.  ``profile`` (optional,
    ``repro.obs.profile.PlannerProfile``-shaped) records batch shape,
    resolved engine and wall time, plus per-stage timings for schemes that
    declared ``accepts_profile`` (fr/ftr: bisection, candidate search,
    witness extraction...) — without changing what is planned.

    Mixed fan-outs (ragged d): when ``nets`` is a sequence of overlays
    whose ``d`` differ, the batch is bucketed by ``d``, each bucket
    planned in one engine call against ``dataclasses.replace(params,
    d=...)`` (same n/k/M/alpha — the code is fixed, the helper count is
    per-failure), and reassembled in input order.  The packed arrays are
    padded to the widest fan-out — row ``b`` of ``betas``/``parents`` is
    meaningful up to that overlay's own ``d`` and zero beyond — and the
    per-network :class:`RepairPlan` objects (each carrying its true ``d``
    via ``plan.params``) always ride along in ``plans``.

    The result's ``engine`` field reports which path actually planned the
    batch; on the scalar path the original :class:`RepairPlan` objects ride
    along in ``plans`` and ``plans_from_batch`` returns them verbatim.
    """
    with span("plan_many"):
        _check_engine(engine)
        spec = get_scheme(scheme)
        is_tensor = isinstance(nets, np.ndarray)
        if not is_tensor:
            nets = list(nets)
            ds = {n.d for n in nets}
            if len(ds) > 1:
                return _plan_ragged(nets, params, scheme, engine=engine,
                                    witness=witness, profile=profile, **kwargs)
        kw = _planner_kwargs(spec, witness, kwargs)
        resolved = _resolve_engine(spec, engine, "plan_many")
        if profile is not None:
            profile.note(scheme=spec.name,
                         batch=int(nets.shape[0]) if is_tensor else len(nets),
                         d=params.d, engine=resolved,
                         fallback=engine not in ("auto", resolved))
        if resolved in ("batched", "jax"):
            planner = spec.batched if resolved == "batched" else spec.jax
            caps = nets if is_tensor else caps_tensor(nets)
            if resolved == "batched" and spec.accepts_profile \
                    and profile is not None:
                kw["profile"] = profile
            with _pstage(profile, "total"):
                return planner(caps, params, **kw)
        net_list = ([OverlayNetwork(c.tolist()) for c in nets] if is_tensor
                    else list(nets))
        with _pstage(profile, "total"):
            plans = [spec.scalar(n, params, **kw) for n in net_list]
        return _batch_from_plans(spec, plans, params)


def _plan_ragged(nets: List[OverlayNetwork], params: CodeParams, scheme: str,
                 engine: str, witness: str, profile,
                 **kwargs) -> BatchPlanResult:
    """Mixed fan-out dispatch: bucket by ``d``, one engine call per bucket,
    reassemble in input order padded to the widest ``d``.

    Each bucket is planned against ``dataclasses.replace(params, d=d_b)``
    — this keeps (n, k, M, alpha) and re-runs parameter validation, so an
    overlay too small for the code (d < k) fails loudly here rather than
    producing a nonsense plan.  Per-bucket results are identical to what a
    single-d :func:`plan_many` call over that sub-batch returns (the
    bucket path *is* that call), so engine guarantees — batched bitwise
    vs scalar, jax within documented tolerance — carry over row by row.
    """
    d_max = max(n.d for n in nets)
    buckets: Dict[int, List[int]] = {}
    for i, n in enumerate(nets):
        buckets.setdefault(n.d, []).append(i)
    if profile is not None:
        profile.note(scheme=scheme, batch=len(nets), ragged=True,
                     d_buckets=sorted(buckets))
    B = len(nets)
    times = np.full(B, np.inf)
    traffic = np.full(B, np.inf)
    betas = np.zeros((B, d_max))
    parents = np.zeros((B, d_max + 1), dtype=np.int64)
    lbs = np.full(B, np.nan)
    plans: List[Optional[RepairPlan]] = [None] * B
    engines = set()
    for db in sorted(buckets):
        idx = buckets[db]
        pb = params if db == params.d else dataclasses.replace(params, d=db)
        sub = plan_many([nets[i] for i in idx], pb, scheme, engine=engine,
                        witness=witness, profile=profile, **kwargs)
        engines.add(sub.engine)
        times[idx] = sub.times
        traffic[idx] = sub.traffic
        betas[np.asarray(idx)[:, None], np.arange(db)[None, :]] = sub.betas
        parents[np.asarray(idx)[:, None],
                np.arange(db + 1)[None, :]] = sub.parents
        if sub.lower_bounds is not None:
            lbs[idx] = sub.lower_bounds
        for i, p in zip(idx, plans_from_batch(sub, pb)):
            plans[i] = p
    return BatchPlanResult(
        scheme, times, traffic, betas, parents,
        lower_bounds=None if np.isnan(lbs).all() else lbs,
        engine=engines.pop() if len(engines) == 1 else "mixed",
        plans=plans)


def _batch_from_plans(spec: SchemeSpec, plans: List[RepairPlan],
                      params: CodeParams) -> BatchPlanResult:
    """Pack scalar plans into a BatchPlanResult (the scalar-fallback path)."""
    d = params.d
    B = len(plans)
    parents = np.zeros((B, d + 1), dtype=np.int64)
    betas = np.zeros((B, d))
    lbs = np.full(B, np.nan)
    for b, p in enumerate(plans):
        for u in range(1, d + 1):
            parents[b, u] = p.parent[u]
        betas[b] = p.betas
        if p.lower_bound is not None:
            lbs[b] = p.lower_bound
    times = np.array([p.time for p in plans], dtype=np.float64)
    traffic = np.array([p.total_traffic for p in plans], dtype=np.float64)
    return BatchPlanResult(spec.name, times, traffic, betas, parents,
                           lower_bounds=None if np.isnan(lbs).all() else lbs,
                           engine="scalar", plans=plans)


# ---------------------------------------------------------------------------
# Built-in schemes (the paper's family)
# ---------------------------------------------------------------------------

register_scheme("star", plan_star, batched=plan_star_batch,
                jax=_lazy_jax("plan_star_jax"), topology="star",
                description="conventional uniform-beta star [3] (baseline)")
register_scheme("fr", plan_fr, batched=plan_fr_batch,
                jax=_lazy_jax("plan_fr_jax"), accepts_witness=True,
                accepts_profile=True, topology="star",
                description="Flexible Regeneration on the star (Section III)")
register_scheme("tr", plan_tr, batched=plan_tr_batch,
                jax=_lazy_jax("plan_tr_jax"), topology="tree",
                description="tree topology, uniform traffic (Algorithm 1)")
register_scheme("ftr", plan_ftr, batched=plan_ftr_batch,
                jax=_lazy_jax("plan_ftr_jax"), accepts_witness=True,
                accepts_profile=True, topology="tree",
                description="flexible traffic on a searched tree (Alg. 2)")
register_scheme("shah", plan_shah, batched=plan_shah_batch, topology="star",
                description="the (beta_max, gamma) scheme of Shah et al. [6]")
register_scheme("rctree", plan_rctree, batched=None, topology="tree",
                description="RCTREE [7], the MDS-violating prior scheme "
                            "(scalar only, declared)")


# ---------------------------------------------------------------------------
# Deprecation shims: the old dispatch tables, backed by the registry
# ---------------------------------------------------------------------------

_deprecation_warned: set = set()


def warn_deprecated(old: str, new: str) -> None:
    """One DeprecationWarning per legacy name per process."""
    if old not in _deprecation_warned:
        _deprecation_warned.add(old)
        warnings.warn(
            f"repro.core.{old} is deprecated; use repro.core.api.{new} "
            f"(the capability-aware scheme registry)", DeprecationWarning,
            stacklevel=4)


class _DeprecatedSchemeMap(Mapping):
    """Read-only live view of the registry behind a legacy dict name.

    Stays in sync with registrations (a newly registered scheme shows up
    immediately) and warns once per process on first use.
    """

    def __init__(self, name: str, replacement: str,
                 view: Callable[[], Dict[str, Callable]]):
        self._name = name
        self._replacement = replacement
        self._view = view

    def _touch(self) -> None:
        warn_deprecated(self._name, self._replacement)

    def __getitem__(self, key: str) -> Callable:
        self._touch()
        return self._view()[key]

    def __iter__(self) -> Iterator[str]:
        self._touch()
        return iter(self._view())

    def __len__(self) -> int:
        return len(self._view())

    def __repr__(self) -> str:  # no warning: repr is for debuggers
        return f"<deprecated {self._name} -> api.{self._replacement}: " \
               f"{sorted(self._view())}>"


SCHEMES = _DeprecatedSchemeMap(
    "SCHEMES", "plan() / get_scheme()",
    lambda: {name: spec.scalar for name, spec in _REGISTRY.items()})

BATCHED_SCHEMES = _DeprecatedSchemeMap(
    "BATCHED_SCHEMES", "plan_many() / get_scheme()",
    lambda: {name: spec.batched for name, spec in _REGISTRY.items()
             if spec.batched is not None})
