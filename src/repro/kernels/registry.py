"""Kernel registry: one uniform handle per kernel in the suite.

Benchmarks, equivalence tests, and the ``ssrcfg`` dispatch layer used to
hand-maintain parallel import lists of the kernel modules; adding a kernel
meant editing four files.  Now a module self-registers at import time:

    @register_kernel("reduction")
    def _entry():
        return KernelEntry(name="reduction", ssr=ssr_dot,
                           baseline=baseline_dot, ref=ref.dot_ref,
                           example=_example)

and every consumer iterates :func:`entries`:

* ``benchmarks/kernel_bench.py`` times each entry's ``ref`` path and smoke-
  runs its ``ssr`` path from the same ``example`` factory;
* ``tests/test_registry.py`` asserts ``ssr == baseline == ref`` per entry on
  non-multiple-of-block sizes;
* ``repro.kernels.ops`` routes its public functions through
  :func:`dispatch`, which consults ``region.ssr_enabled()`` — the software
  ``ssrcfg`` CSR — to pick the streamed or plain-XLA variant.

Entries are lazy (factories resolved on first access) so registration adds
zero import cost and no cycles.
"""

from __future__ import annotations

import dataclasses
import importlib
import os
from typing import Callable, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

from repro.core import resilience
from repro.core.region import ssr_enabled

# Every module under repro.kernels that registers at least one kernel.  This
# is the single place the suite is enumerated; consumers iterate the
# registry, never this tuple.
_KERNEL_MODULES = ("reduction", "scan", "relu", "stencil", "gemv", "gemm",
                   "fft", "bitonic", "attention", "chained", "dag", "sparse")


@dataclasses.dataclass(frozen=True, eq=False)
class KernelEntry:
    """One kernel's public variants.

    ``ssr``      — streamed Pallas kernel (operands delivered by BlockStreams)
    ``baseline`` — monolithic Pallas kernel with explicit in-body loads
                   (``None`` where the paper has no meaningful baseline)
    ``ref``      — pure-jnp oracle, also the ``ssrcfg``-off execution path
    ``cluster``  — multi-core variant (paper §5.3–5.5): same positional
                   args as ``ssr`` plus a ``cores=C`` kwarg, sharded over a
                   ``cores`` device mesh (``None`` where the iteration
                   space has no clean outer split).  Deliberately *not* in
                   :meth:`variants`: it needs a multi-device mesh, which
                   the single-device equivalence suite does not have —
                   ``benchmarks/cluster_bench.py`` and
                   ``tests/test_cluster.py`` enumerate it instead.
    ``example``  — ``example(rng, odd=False) -> (args, kwargs)`` sample-input
                   factory; ``odd=True`` yields non-multiple-of-block sizes
    ``tol``      — allclose tolerances for ssr/baseline-vs-ref comparisons
    ``problem``  — human-readable §4.2 problem description
    ``mosaic_refusal`` — the compiler's reason, for a kernel declared as
                   not compiling through Mosaic (its ``ssr`` raises
                   ``frontend.MosaicUnsupported`` off the interpreter);
                   empty for kernels that run on the chip
    """

    name: str
    ssr: Callable
    ref: Callable
    baseline: Optional[Callable] = None
    cluster: Optional[Callable] = None
    example: Optional[Callable] = None
    tol: Dict[str, float] = dataclasses.field(
        default_factory=lambda: {"rtol": 1e-3, "atol": 1e-3})
    problem: str = ""
    mosaic_refusal: str = ""

    def variants(self) -> Dict[str, Callable]:
        out = {"ssr": self.ssr, "ref": self.ref}
        if self.baseline is not None:
            out["baseline"] = self.baseline
        return out

    def cluster_variants(self) -> Dict[str, Callable]:
        """The multi-core variants, keyed like :meth:`variants`."""
        return {"cluster": self.cluster} if self.cluster is not None else {}


_FACTORIES: Dict[str, Callable[[], KernelEntry]] = {}
_RESOLVED: Dict[str, KernelEntry] = {}


def register_kernel(name: str):
    """Class/function decorator registering a lazy :class:`KernelEntry`."""

    def deco(factory: Callable[[], KernelEntry]):
        if name in _FACTORIES:
            raise ValueError(f"kernel {name!r} registered twice")
        _FACTORIES[name] = factory
        return factory

    return deco


def _ensure_loaded() -> None:
    for mod in _KERNEL_MODULES:
        importlib.import_module(f"repro.kernels.{mod}")


def names() -> List[str]:
    _ensure_loaded()
    return sorted(_FACTORIES)


def get(name: str) -> KernelEntry:
    _ensure_loaded()
    if name not in _RESOLVED:
        if name not in _FACTORIES:
            raise KeyError(
                f"no kernel {name!r}; registered: {sorted(_FACTORIES)}")
        entry = _FACTORIES[name]()
        if entry.name != name:
            raise ValueError(
                f"entry name {entry.name!r} != registered name {name!r}")
        _RESOLVED[name] = entry
    return _RESOLVED[name]


def entries() -> List[KernelEntry]:
    return [get(n) for n in names()]


def _baseline_fallback_enabled() -> bool:
    return os.environ.get("REPRO_BASELINE_FALLBACK", "") not in ("", "0")


def dispatch(name: str, *args, ssr: Optional[bool] = None,
             baseline_fallback: Optional[bool] = None, **kwargs):
    """Run a kernel through the ``ssrcfg`` gate (paper §2.2.2).

    ``ssr=None`` consults :func:`region.ssr_enabled`; semantics are identical
    either way — only the execution engine changes.  A streamed call runs
    inside the profiler span ``ssr.call``; inside it the kernel frontend
    opens ``ssr.launch`` (a cached pipeline) or ``ssr.build`` (a new one).

    ``baseline_fallback`` is the last rung of the degradation ladder
    (tuned → default schedule → baseline): when the streamed variant fails
    with a *typed* dispatch error (injected fault, cache I/O,
    ``LoweringError``, compile failure) even after the lowering layer's own
    schedule degradation, re-run the call through the plain-XLA ``ref``
    variant — the paper's ``ssrcfg``-off path, always available because SSR
    is non-invasive.  Opt-in (``baseline_fallback=True`` or env
    ``REPRO_BASELINE_FALLBACK=1``) because it can mask a broken streamed
    engine in exchange for availability; genuine numerics/user errors
    (``TypeError``, shape ``ValueError``…) always propagate.
    """
    use = ssr_enabled() if ssr is None else ssr
    if not use:
        return get(name).ref(*args, **kwargs)
    with TraceAnnotation("ssr.call"):
        entry = get(name)
        if baseline_fallback is None:
            baseline_fallback = _baseline_fallback_enabled()
        if not baseline_fallback:
            return entry.ssr(*args, **kwargs)
        try:
            return entry.ssr(*args, **kwargs)
        except resilience.fallback_error_types() as e:
            resilience.record_fallback(
                seam=resilience.classify(e), site=f"registry:{name}",
                error=e, from_schedule="ssr", to_schedule="baseline")
            return entry.ref(*args, **kwargs)
