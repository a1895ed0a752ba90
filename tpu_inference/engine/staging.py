"""Packed step-program operands: one int32 array a dispatch.

A step program's small per-dispatch operands (tokens, context lengths,
block tables, sampling fields, the penalty window, the step number its
sampling key is folded from) travel to the device as ONE ``[lanes,
width]`` int32 array: a lane a row, a field a fixed range of columns.
The host writes the fields through numpy views of that array
(``views``), the jitted program slices the same columns back out
(``unpack``). float32 fields ride as their BITS (a float32 view of the
int32 columns on the host, ``bitcast_convert_type`` in the graph), so
every value reaches the graph exactly as a per-field transfer would
have carried it.

The columns are Python ints fixed by the block-table width (and, for a
prefill, the bucket): nothing about a layout is traced, and a program
reads its lanes and its bucket off the operand's shape.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from tpu_inference.engine.sampling import PENALTY_WINDOW

_I, _F = np.int32, np.float32


class PackedLayout:
    """Columns of one packed operand array. ``fields`` is a sequence of
    (name, width, dtype, default): width 0 is a scalar a lane (unpacked
    as ``[lanes]``), width n > 0 a row a lane (``[lanes, n]``); dtype is
    int32 or float32; default is what a lane nobody filled reads."""

    def __init__(self, fields):
        self.cols: Dict[str, Tuple[int, int, type]] = {}
        row = []
        for name, width, dtype, default in fields:
            self.cols[name] = (len(row), width, dtype)
            bits = np.asarray(default, dtype).view(_I)
            row += [int(bits)] * max(1, width)
        self.width = len(row)
        self._row = np.asarray(row, _I)

    def blank(self, lanes: int) -> np.ndarray:
        """A fresh ``[lanes, width]`` array, every field at its default."""
        return np.tile(self._row, (lanes, 1))

    def _split(self, packed, as_float) -> dict:
        out = {}
        for name, (c0, width, dtype) in self.cols.items():
            v = packed[:, c0:c0 + width] if width else packed[:, c0]
            out[name] = as_float(v) if dtype is _F else v
        return out

    def views(self, packed: np.ndarray) -> Dict[str, np.ndarray]:
        """Writable numpy views of ``packed``'s fields, by name."""
        return self._split(packed, lambda v: v.view(_F))

    def unpack(self, packed: jax.Array) -> Dict[str, jax.Array]:
        """The same fields inside a jitted program: static slices, and a
        bitcast for the float32 ones."""
        return self._split(
            packed, lambda v: jax.lax.bitcast_convert_type(v, jnp.float32))


def _sampling(lanes_plural: bool):
    """The sampling fields every step program takes, under the names
    its stager uses (a decode batch's are plural)."""
    s = "s" if lanes_plural else ""
    return [("top_k" + s, 0, _I, 0), ("seed" + s, 0, _I, -1),
            ("rlast" + s, 0, _I, 0), ("temp" + s, 0, _F, 0.0),
            ("top_p" + s, 0, _F, 1.0), ("rpen" + s, 0, _F, 1.0)]


def decode_layout(bt_width: int) -> PackedLayout:
    """A decode rung's operands (a lane = a batch slot). ``carried``
    marks the lanes whose token and window come from the newest call
    still in flight (read only where the pipeline is deeper than 1);
    ``step`` says the same number on every lane."""
    return PackedLayout(
        [("tokens", 0, _I, 0), ("ctx", 0, _I, 0), ("allowed", 0, _I, 0),
         ("eos_ids", 0, _I, -1), ("carried", 0, _I, 0), ("step", 0, _I, 0)]
        + _sampling(True)
        + [("bts", bt_width, _I, 0), ("windows", PENALTY_WINDOW, _I, -1)])


def prefill_layout(bucket: int, bt_width: int) -> PackedLayout:
    """A prefill's operands (a lane = a prompt or a chunk of one). An
    unfilled lane has prompt_len 1 and an all-zero block table: its one
    write lands on the trash page."""
    return PackedLayout(
        [("tokens", bucket, _I, 0), ("prompt_len", 0, _I, 1),
         ("prefix_len", 0, _I, 0), ("step", 0, _I, 0)]
        + _sampling(False)
        + [("block_table", bt_width, _I, 0),
           ("window", PENALTY_WINDOW, _I, -1)])


def prefill_bucket(width: int, bt_width: int) -> int:
    """The bucket of a prefill operand ``width`` columns wide."""
    return width - (prefill_layout(1, bt_width).width - 1)
