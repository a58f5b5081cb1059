"""Kernel registry: named implementations per op, priority dispatch,
and an inspectable record of every routing decision.

Ops (``nm_matmul``, ``indexmac_gather``) register candidate
implementations with a ``supports(ctx) -> None | str`` predicate (None
means "I can run this"; a string is the human-readable reason it
cannot). ``dispatch`` walks candidates in descending priority, runs the
first supported one, and appends a :class:`DispatchRecord` to a bounded
history — tests and the serving engine use the record to assert *which*
path executed (e.g. that an odd transformer shape really hit the padded
Pallas kernel rather than silently falling back to the dense reference).

Dispatch happens at trace time: under ``jax.jit`` one record is written
per compilation, not per call — the routing is shape-static, so one
record per compiled shape is the complete story.

Every implementation is registered for a **backend** (``"tpu"``,
``"gpu"``, or ``"any"`` for the backend-neutral XLA references); the
dispatch context carries the resolved backend of the call (see
:mod:`repro.kernels.backend`) and implementations registered for a
*different* backend are filtered out silently — they are not
"unsupported on this call", they are a different lowering of the same
family, so they never pollute the record's ``reason`` string.

Alongside the bounded record history the registry keeps **per-family
dispatch counters** (``dispatch_counts``): a ``(op, impl, backend) ->
count`` map that never evicts, so tests assert "the decode family
dispatched N times on the tpu backend and the reference route zero
times" without sniffing the record list. When observability is enabled
(:mod:`repro.obs`) every routing decision also increments the
``kernel_dispatch_total{op=,impl=,backend=}`` metric.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
from typing import Any, Callable, Optional

from repro import obs as _obs


@dataclasses.dataclass(frozen=True)
class DispatchRecord:
    """One routing decision (newest-last in the history).

    Public surface (re-exported as ``repro.api.DispatchRecord``): tests,
    benchmarks and fleet monitoring consume these fields — treat them as
    frozen. ``repro.api.explain_dispatch`` returns the same shape for a
    dry-run routing query (no kernel executed).
    """

    op: str  # dispatch family, e.g. "nm_matmul_decode_q"
    impl: str  # chosen implementation, e.g. "pallas_decode_q"
    shape: tuple  # logical (M, K, N)
    padded: Optional[tuple]  # (M', K', N') when the impl padded, else None
    block: Optional[tuple]  # (block_m, block_n, block_k) when applicable
    reason: str  # why higher-priority impls were skipped ("" if none)
    backend: str = "tpu"  # resolved kernel backend the call routed under
    interpret: bool = False  # True when a Pallas impl ran in the interpreter


class KernelForceError(RuntimeError):
    """KernelPolicy("force") demanded the Pallas kernel but the shape
    cannot normalize to any legal kernel geometry. Raised instead of a
    silent fall-through to the reference path — a forced weight that
    quietly serves XLA timings is a corrupted benchmark, not a fallback.
    """


@dataclasses.dataclass(frozen=True)
class KernelImpl:
    op: str
    name: str
    priority: int
    supports: Callable[[dict], Optional[str]]
    run: Callable[..., Any]
    uses_plan: bool = False  # True: records carry ctx["plan"] geometry
    backend: str = "any"  # "tpu" | "gpu" | "any" (backend-neutral refs)


_LOCK = threading.Lock()
_IMPLS: dict[str, list[KernelImpl]] = {}
_HISTORY: collections.deque[DispatchRecord] = collections.deque(maxlen=256)
_COUNTS: collections.Counter = collections.Counter()  # (op, impl, backend) -> n


def make_ctx(shape, *, nm, use_kernel: bool, plan=None, dtype=None,
             force: bool = False, backend: str = "tpu", **extra) -> dict:
    """Dispatch context for a compressed-GEMM op.

    ``shape`` is the logical (M, K, N); ``nm`` the NMConfig of the
    compressed operand; ``force=True`` tells padded impls to ignore the
    waste limit (KernelPolicy mode "force"); ``backend`` is the
    *resolved* kernel backend of the call (see
    :mod:`repro.kernels.backend` — never ``"auto"`` here). Extra keys
    (e.g. the gather port's ``tileable``) pass through to ``supports``
    predicates.
    """
    return {"shape": tuple(shape), "cfg": nm, "use_kernel": use_kernel,
            "plan": plan, "dtype": dtype, "force": force,
            "backend": backend, **extra}


def weight_ctx(w, shape, *, plan=None, dtype=None, backend=None,
               **extra) -> dict:
    """Dispatch context derived from a typed weight node's own metadata
    (:class:`NMWeight` or its quantized sibling — anything carrying
    ``nm`` and ``kernel_policy``) — the weight, not the call site,
    decides nm / kernel policy / backend. ``backend`` overrides the
    policy's (already-resolved callers); ``None`` resolves the policy's
    static field here."""
    from repro.kernels.backend import resolve_backend

    pol = w.kernel_policy
    if backend is None:
        backend = resolve_backend(getattr(pol, "backend", "auto"))
    return make_ctx(shape, nm=w.nm, use_kernel=pol.mode != "off",
                    plan=plan, dtype=dtype, force=pol.mode == "force",
                    backend=backend, **extra)


def register(
    op: str,
    name: str,
    *,
    priority: int = 0,
    supports: Callable[[dict], Optional[str]] = lambda ctx: None,
    uses_plan: bool = False,
    backend: str = "any",
):
    """Decorator registering ``fn`` as implementation ``name`` of ``op``
    for ``backend`` (``"any"`` = backend-neutral, e.g. XLA references)."""

    def deco(fn):
        impl = KernelImpl(op, name, priority, supports, fn, uses_plan,
                          backend)
        with _LOCK:
            impls = [i for i in _IMPLS.get(op, ()) if i.name != name]
            impls.append(impl)
            impls.sort(key=lambda i: -i.priority)
            _IMPLS[op] = impls
        return fn

    return deco


def implementations(op: str) -> tuple[KernelImpl, ...]:
    with _LOCK:
        return tuple(_IMPLS.get(op, ()))


def dispatch(op: str, ctx: dict, *args, **kwargs):
    """Run the highest-priority supported implementation of ``op``.

    ``ctx`` must carry ``shape=(M, K, N)``; when the chosen impl is a
    padded kernel, ``ctx["plan"]`` (a PadPlan) supplies the padded
    geometry recorded alongside. Implementations registered for a
    different backend than ``ctx["backend"]`` are filtered silently
    (they are a parallel lowering, not a fallback reason).
    """
    backend = ctx.get("backend", "tpu")
    skipped = []
    for impl in implementations(op):
        if impl.backend not in ("any", backend):
            continue
        why = impl.supports(ctx)
        if why is not None:
            skipped.append(f"{impl.name}: {why}")
            continue
        out = impl.run(*args, **kwargs)
        plan = ctx.get("plan")
        uses_plan = plan is not None and impl.uses_plan
        _record(
            DispatchRecord(
                op=op,
                impl=impl.name,
                shape=tuple(ctx.get("shape", ())),
                padded=plan.padded_shape if uses_plan else None,
                block=plan.block if uses_plan else None,
                reason="; ".join(skipped),
                backend=backend,
                interpret=(impl.backend != "any"
                           and bool(kwargs.get("interpret"))),
            )
        )
        return out
    raise LookupError(
        f"no implementation of {op!r} supports this call on backend "
        f"{backend!r}: {'; '.join(skipped)}"
    )


def explain(op: str, ctx: dict) -> DispatchRecord:
    """The :class:`DispatchRecord` ``dispatch`` *would* write for this
    context, without running anything — the dry-run behind
    ``repro.api.explain_dispatch``. Raises LookupError when no
    implementation supports the call (same contract as dispatch)."""
    backend = ctx.get("backend", "tpu")
    skipped = []
    for impl in implementations(op):
        if impl.backend not in ("any", backend):
            continue
        why = impl.supports(ctx)
        if why is not None:
            skipped.append(f"{impl.name}: {why}")
            continue
        plan = ctx.get("plan")
        uses_plan = plan is not None and impl.uses_plan
        return DispatchRecord(
            op=op,
            impl=impl.name,
            shape=tuple(ctx.get("shape", ())),
            padded=plan.padded_shape if uses_plan else None,
            block=plan.block if uses_plan else None,
            reason="; ".join(skipped),
            backend=backend,
        )
    raise LookupError(
        f"no implementation of {op!r} supports this call on backend "
        f"{backend!r}: {'; '.join(skipped)}"
    )


def _record(rec: DispatchRecord) -> None:
    with _LOCK:
        _HISTORY.append(rec)
        _COUNTS[(rec.op, rec.impl, rec.backend)] += 1
    bundle = _obs.get_obs()
    if bundle is not None:
        bundle.metrics.inc("kernel_dispatch_total", op=rec.op,
                           impl=rec.impl, backend=rec.backend)


def last_dispatch(op: Optional[str] = None) -> Optional[DispatchRecord]:
    """Most recent record (for ``op`` if given), or None."""
    with _LOCK:
        for rec in reversed(_HISTORY):
            if op is None or rec.op == op:
                return rec
    return None


def dispatch_history(op: Optional[str] = None) -> list[DispatchRecord]:
    with _LOCK:
        return [r for r in _HISTORY if op is None or r.op == op]


def dispatch_counts(op_prefix: Optional[str] = None,
                    backend: Optional[str] = None) -> dict:
    """Cumulative ``(op, impl, backend) -> count`` of every routing
    decision made since process start (or :func:`clear_history`). Unlike
    the bounded record history this never evicts — the supported way for
    tests and monitoring to assert which families executed on which
    backend (e.g. decode-family count > 0, reference-route count == 0,
    everything under ``backend="gpu"``)."""
    with _LOCK:
        return {k: v for k, v in _COUNTS.items()
                if (op_prefix is None or k[0].startswith(op_prefix))
                and (backend is None or k[2] == backend)}


def clear_history() -> None:
    """Reset both the bounded record history and the dispatch counters."""
    with _LOCK:
        _HISTORY.clear()
        _COUNTS.clear()
