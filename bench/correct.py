"""The comparison that decides ``correct``, and the lower-precision controls.

Every request answered in the window is compared with the plain reference's
logits for its input: ``rms(y - ref) / rms(ref)`` over the request's logits.
The number held against the configuration's limit is the worst request's
(``worst_rms_rel``). A request that failed or never came counts apart
(``failed``, limit 0); ``judge`` holds both, for the harness and for the
controls alike.

A control is the reference itself, computed in the nearest precision below
the one that the configuration states, with every convolution's and dense
layer's operands rounded through it (``CONTROLS``). It has to read above the
limit; ``control.py`` reads it on the chip and ``tests/`` at a small size,
there also served in the program's place through the whole harness.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def rms_rel(y: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per row: rms of the difference over rms of the reference."""
    y = np.asarray(y, np.float64)
    ref = np.asarray(ref, np.float64)
    return (np.sqrt(np.mean((y - ref) ** 2, axis=-1))
            / np.sqrt(np.mean(ref ** 2, axis=-1)))


def worst_rms_rel(answers: dict, ref: np.ndarray, chunk: int = 4096) -> float:
    """The largest ``rms_rel`` over the distinct answers (``WindowLog.answers``:
    ``(pool index, float32 logits bytes) -> count``), so over every answered
    request; ``inf`` where one has the wrong length or is not finite, ``nan``
    where there is none."""
    keys = list(answers)
    if not keys:
        return float("nan")
    worst = 0.0
    for k in range(0, len(keys), chunk):
        part = keys[k:k + chunk]
        rows = [np.frombuffer(b, np.float32) for _, b in part]
        if any(r.shape != ref.shape[1:] for r in rows):
            return float("inf")
        y = np.stack(rows)
        if not np.all(np.isfinite(y)):
            return float("inf")
        idx = np.array([i for i, _ in part])
        worst = max(worst, float(np.max(rms_rel(y, ref[idx]))))
    return worst


def judge(worst: float, answered: int, failed: int, limit: float) -> bool:
    """``correct``: some request answered, none failed, and the worst
    answer within the limit (a ``nan`` or ``inf`` reading is never)."""
    return bool(answered > 0 and failed == 0 and worst <= limit)


def answers_of(rows: np.ndarray, pool_idx) -> dict:
    """``WindowLog.answers`` for logits ``rows`` answered for ``pool_idx``."""
    out: dict = {}
    for i, y in zip(pool_idx, np.asarray(rows, np.float32)):
        key = (int(i), np.ascontiguousarray(y.reshape(-1)).tobytes())
        out[key] = out.get(key, 0) + 1
    return out


# -- controls: fake quantization of the reference's matmul operands ---------

def _fp8_e4m3(x, axes=None):
    """Per-tensor scaled to float8 e4m3's range (448), rounded, scaled back."""
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _int4(x, axes=None):
    """Symmetric int4 ([-7, 7]): per tensor, or per output channel over
    ``axes`` (the weights' input axes)."""
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=axes is not None)
    s = jnp.where(amax > 0, amax / 7.0, 1.0)
    return jnp.clip(jnp.round(x / s), -7, 7) * s


def _int4_weight(w):
    return _int4(w, axes=tuple(range(w.ndim - 1)))


# name -> (activation rounding, weight rounding)
CONTROLS = {
    "fp8_e4m3": (_fp8_e4m3, _fp8_e4m3),
    "int4": (_int4, _int4_weight),
}


def logits(model, params, pool: np.ndarray, sizes: dict, *, control=None,
           block: int = 8) -> np.ndarray:
    """The reference's logits for ``pool``, ``block`` inputs at a time, at
    ``highest`` precision, or the named control's."""
    act, weight = CONTROLS[control] if control else (None, None)
    fwd = jax.jit(lambda p, x: model.forward(p, x, sizes, act=act,
                                             weight=weight))
    return np.concatenate([np.asarray(fwd(params, jnp.asarray(pool[i:i + block])))
                           for i in range(0, len(pool), block)])
