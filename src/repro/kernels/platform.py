"""Platform detection + execution-mode defaults for the ICQ kernel layer.

Central policy knob for every Pallas entry point in this package:

  * ``detected_platform()``  — jax default backend ('tpu' | 'cpu' | 'gpu'),
    overridable with ``ICQ_PLATFORM``. A backend that fails to
    initialize raises: nothing here guesses a platform.
  * ``default_interpret()``  — Pallas kernels compile natively on TPU and
    fall back to ``interpret=True`` everywhere else. ``ICQ_INTERPRET=0/1``
    forces either mode.
  * ``default_backend()``    — which dispatch arm family the execution
    layer prefers when the caller does not say: the Pallas kernels on
    TPU, the pure-XLA prepared path elsewhere (interpret-mode Pallas is
    a correctness tool, not a serving path). ``ICQ_BACKEND=pallas|xla``
    overrides.
  * ``decode_m_threshold()`` — largest M routed to the fused
    dequant+matmul kernel; bigger batches dequantize once per call and
    ride the dense MXU matmul. ``ICQ_DECODE_M`` overrides.
  * ``default_runtime_fmt()`` — prepared-weight runtime format:
    'v2' (checkpointed gap stream, ~0.3-0.45 b/w outlier overhead) by
    default, 'v1' (dense 1-bit selector bitmap, ~1 b/w) as the
    bitwise-parity fallback. ``ICQ_RUNTIME_FMT=v1|v2`` overrides.
  * ``default_accum_dtype()`` — dtype of the fused matmul kernels'
    VMEM accumulator scratch: 'f32' (default, exact) or 'bf16' (halves
    the accumulator VMEM term; partial sums round to bf16 per K-step).
    ``ICQ_ACCUM_DTYPE=f32|bf16`` overrides.
  * ``default_paged_attn()`` — which arm serves paged-KV decode
    attention: the Pallas paged-attention kernel ('pallas', default on
    TPU — streams only live KV blocks through VMEM) or the XLA
    gather-the-logical-view path ('xla', default elsewhere; also the
    bitwise-exact fault-tolerance degrade target).
    ``ICQ_PAGED_ATTN=pallas|xla`` overrides.
"""
from __future__ import annotations

import os

import jax

_TRUTHY = ("1", "true", "yes", "on")
_FALSY = ("0", "false", "no", "off")


def detected_platform() -> str:
    override = os.environ.get("ICQ_PLATFORM")
    if override:
        return override.lower()
    return jax.default_backend()


def default_interpret() -> bool:
    """Interpret only off-TPU (satellite: no caller passes this anymore)."""
    env = os.environ.get("ICQ_INTERPRET")
    if env:  # set-but-empty means unset (CI YAML / shell expansion)
        if env.lower() in _TRUTHY:
            return True
        if env.lower() in _FALSY:
            return False
        raise ValueError(
            f"ICQ_INTERPRET must be one of {_TRUTHY + _FALSY}, got {env!r}")
    return detected_platform() != "tpu"


def default_backend() -> str:
    """'pallas' on TPU, 'xla' elsewhere; ICQ_BACKEND overrides."""
    env = os.environ.get("ICQ_BACKEND")
    if env:
        env = env.lower()
        if env not in ("pallas", "xla"):
            raise ValueError(f"ICQ_BACKEND must be 'pallas' or 'xla', got {env!r}")
        return env
    return "pallas" if detected_platform() == "tpu" else "xla"


def default_runtime_fmt() -> str:
    """'v2' checkpointed-stream runtime unless ICQ_RUNTIME_FMT says 'v1'."""
    env = os.environ.get("ICQ_RUNTIME_FMT")
    if env:  # set-but-empty means unset
        env = env.lower()
        if env not in ("v1", "v2"):
            raise ValueError(
                f"ICQ_RUNTIME_FMT must be 'v1' or 'v2', got {env!r}")
        return env
    return "v2"


def default_accum_dtype() -> str:
    """'f32' (exact) or 'bf16' (half-size matmul accumulator scratch)."""
    env = os.environ.get("ICQ_ACCUM_DTYPE")
    if not env:  # unset or set-but-empty
        return "f32"
    env = env.lower()
    if env not in ("f32", "bf16"):
        raise ValueError(
            f"ICQ_ACCUM_DTYPE must be 'f32' or 'bf16', got {env!r}")
    return env


def default_paged_attn() -> str:
    """'pallas' on TPU, 'xla' elsewhere; ICQ_PAGED_ATTN overrides."""
    env = os.environ.get("ICQ_PAGED_ATTN")
    if env:  # set-but-empty means unset
        env = env.lower()
        if env not in ("pallas", "xla"):
            raise ValueError(
                f"ICQ_PAGED_ATTN must be 'pallas' or 'xla', got {env!r}")
        return env
    return "pallas" if detected_platform() == "tpu" else "xla"


def decode_m_threshold() -> int:
    """M at or below this routes to the fused icq_matmul kernel."""
    env = os.environ.get("ICQ_DECODE_M")
    if not env:  # unset or set-but-empty
        return 32
    try:
        return int(env)
    except ValueError:
        raise ValueError(f"ICQ_DECODE_M must be an integer, got {env!r}")
