"""One host-to-device put per serving dispatch.

Everything a step program needs from the host each step (a block-table
row per slot, lengths, the last token, the sampling knobs, ...) is a few
hundred bytes to a few KiB, and a put costs the host about the same
whatever it carries (twelve arrays a decode step took the host 3.0 ms
with the device idle, one takes 0.33: PERF.md, PR 32). So the engine
packs the step's host state into ONE fresh int32 array, puts it once, and
the program slices the fields back out.

Floats and unsigned integers travel by bit pattern (``ndarray.view`` on
the host, ``lax.bitcast_convert_type`` in the program): a float32
temperature, or a uint32 seed above 2**31, comes out bit for bit. The
step's packed result does the same in the other direction.

A layout is fixed by the geometry an engine is built with; nothing here
is chosen at run time.
"""
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: what a field may be on the host: 32 bits that view as int32, or a flag
_WIRE_DTYPES = (np.int32, np.uint32, np.float32, np.bool_)


class PackedArgs:
    """Layout of one packed argument array: named fields side by side on
    the last axis, each ``(name, width, dtype)``. A field of width 1 is a
    column and unpacks without that axis; a wider one keeps it."""

    def __init__(self, *fields: Tuple[str, int, type]):
        self.fields = tuple((name, int(width), np.dtype(dtype))
                            for name, width, dtype in fields)
        for name, width, dtype in self.fields:
            if dtype not in _WIRE_DTYPES or width < 1:
                raise ValueError(
                    f"field {name!r}: width {width}, dtype {dtype} cannot "
                    "ride an int32 array")
        self.width = sum(width for _, width, _ in self.fields)

    def nbytes(self, *lead: int) -> int:
        """Bytes one put of this layout sends, ``lead`` rows of it."""
        return math.prod(lead) * self.width * 4

    def pack(self, *lead: int, **values) -> np.ndarray:
        """A NEW int32 host array ``[*lead, width]`` holding ``values``:
        a numpy array or scalar per field, already of the field's dtype
        (a silent cast would change a bit pattern); names the layout
        lacks are ignored. The dispatch that takes it is asynchronous and
        may alias host memory, so the array is never written again; the
        mirrors it was filled from may be."""
        out = np.empty(lead + (self.width,), np.int32)
        at = 0
        for name, width, dtype in self.fields:
            v = values[name]
            if v.dtype != dtype:
                raise TypeError(
                    f"field {name!r} is {v.dtype}, its layout says {dtype}")
            v = v.astype(np.int32) if dtype == np.bool_ else v.view(np.int32)
            if width == 1:
                out[..., at] = v
            else:
                out[..., at:at + width] = v
            at += width
        return out

    def unpack(self, packed) -> Dict[str, jnp.ndarray]:
        """The fields of ``packed`` (traced inside a step program, or any
        array), each in its own dtype again."""
        out = {}
        at = 0
        for name, width, dtype in self.fields:
            v = (packed[..., at] if width == 1
                 else packed[..., at:at + width])
            if dtype == np.bool_:
                v = v != 0
            elif dtype != np.int32:
                v = jax.lax.bitcast_convert_type(v, dtype)
            out[name] = v
            at += width
        return out
