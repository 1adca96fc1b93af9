"""Pallas backend: the JAX backend with counter-hash synthesis kernels.

Extends :class:`JaxBackend` by routing the two synthesis-grid ops
(``synth_window``, ``forecast_noise_z``) through the Pallas kernels in
:mod:`repro.kernels.counter_hash` — one ``pallas_call`` tiled over
rows × steps per window, everything else (probes, admissions, reach
state) inherited from the fused-jit path. Same bit-exactness contract,
same dispatch budget: one tick per window.

The kernels mix uint64 and run in interpreter mode off the TPU (see the
kernel module docstring). The v5e compiler refuses them, so this backend,
like ``backend="jax"``, raises when constructed on a TPU: it is a
*correctness anchor* for a future 32-bit-limb TPU lowering (ROADMAP S4)
rather than a speedup over ``backend="jax"`` today.
"""
from __future__ import annotations

import numpy as np

import jax

from .jax_backend import (_DEVICE_MIN_ROWS, _U64, JaxBackend, _bucket,
                          _pad_rows)


class PallasBackend(JaxBackend):
    name = "pallas"
    _TPU_REFUSED = ("Mosaic cannot lower the uint64 lanes of the "
                    "counter-hash kernels piece_window / forecast_z, and "
                    "XLA:TPU refuses the inherited round24 fence")

    def synth_window(self, levels, slot, fold, rows, t0, amp):
        from ..kernels import ops
        R, W = slot.shape
        if R * W < _DEVICE_MIN_ROWS:
            return super().synth_window(levels, slot, fold, rows, t0, amp)
        rp, wp = _bucket(R), _bucket(W)
        levels_p = _pad_rows(np.ascontiguousarray(levels), rp)
        slot_p = np.zeros((rp, wp), dtype=np.int64)
        slot_p[:R, :W] = slot
        rows_p = _pad_rows(np.asarray(rows, dtype=np.uint64), rp)
        self._tick("synth_window")
        with jax.enable_x64(True):
            out = ops.piece_window(levels_p, slot_p, _U64(fold), rows_p,
                                   np.int64(t0), np.float32(amp))
            return np.asarray(out)[:R, :W]

    def forecast_noise_z(self, fc_fold, rows, now, horizon, std):
        from ..kernels import ops
        rows = np.asarray(rows, dtype=np.uint64)
        if rows.size * horizon < _DEVICE_MIN_ROWS:
            return super().forecast_noise_z(fc_fold, rows, now, horizon, std)
        rp, hp = _bucket(rows.size), _bucket(horizon)
        std_b = np.zeros(hp, dtype=np.float32)
        std_b[:horizon] = np.broadcast_to(
            np.asarray(std, dtype=np.float32), (horizon,))
        self._tick("forecast_noise_z")
        with jax.enable_x64(True):
            out = ops.forecast_z(_U64(fc_fold), _pad_rows(rows, rp),
                                 _U64(now), std_b)
            # explicit copy: callers apply np.exp(z, out=z) in place
            return np.array(np.asarray(out)[:rows.size, :horizon])
