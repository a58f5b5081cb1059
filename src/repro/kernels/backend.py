"""Backend axis for kernel dispatch: which *kernel family* runs a call.

The registry grew out of a single Pallas-on-TPU lowering, which let
TPU-shaped assumptions (tile geometry, VMEM scratch, SMEM scalar reads)
leak into call sites. The follow-up work (arXiv 2501.10189,
arXiv 2305.05559) shows the indexed-MAC idea spans ISAs — the software
mirror is an explicit backend axis: every kernel implementation is
registered *for* a backend, and selection is a first-class API concern
instead of an implicit "whatever Pallas TPU emits".

Two kernel backends exist:

  tpu   the original family (:mod:`repro.kernels.indexmac` /
        ``indexmac_gather``): Mosaic lowering, VMEM scratch
        accumulators, SMEM scalar index reads. It runs compiled on a
        TPU and in the Pallas interpreter on the CPU (the test suite);
        on any other platform it is unavailable, so a chip host whose
        JAX came up on something else fails instead of serving.
  gpu   the Pallas-on-Triton family (:mod:`repro.kernels.indexmac_gpu`):
        grid over output tiles only (every grid dim is a parallel
        program instance — there is no sequential-grid accumulator), the
        K reduction lives inside the kernel, no TPU memory spaces.
        Available on a CUDA/ROCm host, or anywhere when
        ``REPRO_GPU_INTERPRET=1`` opts into the interpreter (the CI
        ``gpu-interpret`` lane).

Resolution order for a call (``repro.api.nm_matmul`` / the weight's
:class:`repro.core.nmweight.KernelPolicy`):

  1. an explicit per-call ``backend=`` argument,
  2. the weight policy's static ``backend`` field,
  3. ``$REPRO_BACKEND`` (consulted only when 1-2 say ``"auto"``),
  4. the device platform (``jax.default_backend()``): a GPU host
     resolves to ``gpu``, everything else to ``tpu``.

Forcing an *unavailable* backend raises the typed
:class:`repro.kernels.registry.KernelForceError` naming the backend —
the same no-silent-fallback contract ``KernelPolicy("force")`` already
enforces for shapes. ``auto`` never raises: the platform default is
available by construction.
"""
from __future__ import annotations

import os
from typing import Optional

import jax

__all__ = [
    "BACKENDS",
    "KERNEL_BACKENDS",
    "backend_unavailable_reason",
    "gpu_interpret_opt_in",
    "platform_backend",
    "resolve_backend",
]

# the values a policy / call / $REPRO_BACKEND may carry
BACKENDS = ("auto", "tpu", "gpu")
# the values resolution produces (and registrations declare)
KERNEL_BACKENDS = ("tpu", "gpu")


def gpu_interpret_opt_in() -> bool:
    """True when ``REPRO_GPU_INTERPRET=1`` opts the GPU kernel family
    into the Pallas interpreter on a host without GPU devices (the CI
    ``gpu-interpret`` lane and the parity test suite)."""
    return os.environ.get("REPRO_GPU_INTERPRET") == "1"


def platform_backend() -> str:
    """The kernel backend the device platform implies: ``gpu`` on a
    CUDA/ROCm host, ``tpu`` everywhere else (on CPU the TPU family runs
    in the Pallas interpreter — the historical default)."""
    return "gpu" if jax.default_backend() == "gpu" else "tpu"


def _validate(value: str, source: str) -> str:
    if value not in BACKENDS:
        raise ValueError(
            f"unknown kernel backend {value!r} from {source}; expected one "
            f"of {BACKENDS}")
    return value


def backend_unavailable_reason(backend: str) -> Optional[str]:
    """None when ``backend`` can execute on this host, else the
    human-readable reason (used both for the typed force error and for
    registry skip diagnostics)."""
    if backend == "tpu":
        if jax.default_backend() in ("tpu", "cpu"):
            return None
        return ("the tpu kernel family runs only on a TPU (compiled) or "
                "the CPU (interpreted); jax.default_backend()="
                f"{jax.default_backend()!r}")
    if backend == "gpu":
        if jax.default_backend() == "gpu" or gpu_interpret_opt_in():
            return None
        return ("no GPU devices visible (jax.default_backend()="
                f"{jax.default_backend()!r}) and REPRO_GPU_INTERPRET!=1")
    return f"unknown backend {backend!r}"


def resolve_backend(requested: Optional[str] = None, *,
                    check: bool = True) -> str:
    """Resolve ``auto``/``tpu``/``gpu``/None to a concrete kernel backend.

    ``None`` and ``"auto"`` defer to ``$REPRO_BACKEND`` and then the
    device platform. With ``check=True`` (the default) an explicitly
    requested backend that cannot execute here raises the typed
    :class:`repro.kernels.registry.KernelForceError` naming the backend
    — auto resolution never raises.
    """
    from repro.kernels.registry import KernelForceError

    source = "call/policy"
    value = requested if requested is not None else "auto"
    _validate(value, source)
    if value == "auto":
        env = os.environ.get("REPRO_BACKEND")
        if env:
            value, source = _validate(env, "$REPRO_BACKEND"), "$REPRO_BACKEND"
    if value == "auto":
        return platform_backend()
    if check:
        why = backend_unavailable_reason(value)
        if why is not None:
            raise KernelForceError(
                f"kernel backend {value!r} (from {source}) cannot execute "
                f"on this host: {why}")
    return value


def interpret_for(backend: str) -> bool:
    """Whether a Pallas kernel of ``backend`` must run interpreted on
    this host: the TPU family interprets only on the CPU and raises on
    any platform other than TPU or CPU; the GPU family interprets off-GPU
    (reachable only under the explicit ``REPRO_GPU_INTERPRET=1``
    opt-in)."""
    platform = jax.default_backend()
    if backend == "tpu":
        why = backend_unavailable_reason("tpu")
        if why is not None:
            from repro.kernels.registry import KernelForceError

            raise KernelForceError(why)
        return platform == "cpu"
    return platform != backend
