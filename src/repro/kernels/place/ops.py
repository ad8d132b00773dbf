"""Public op: capacity-window place step (Pallas kernel or oracle)."""
from __future__ import annotations

from repro.core.device import on_tpu

from . import place as _kernel
from . import ref as _ref

BIG = _ref.BIG


def place_window(C, cap, prefix, *, tiles=None):
    kw = {}
    if tiles is not None:
        kw = dict(v_tile=tiles[0], k_out_tile=tiles[1])
    return _kernel.place_window_pallas(C, cap, prefix,
                                       interpret=not on_tpu(), **kw)


def place_window_ref(C, cap, prefix):
    return _ref.place_window_ref(C, cap, prefix)
