"""One host-to-device transfer a dispatch: the paged engine's staged operands.

A build of ``PagedBatchedDecodeEngine`` (a prefill chunk, a decode step, a
speculative verify step) hands its program nine to twelve small per-row
operands: tokens, positions, block tables, fold counters, the sampling state
(greedy flag, temperature, top-k, top-p, PRNG key words), the slot of each
row's recurrent state, the tenant slots. Each one transferred by itself
costs a host-to-device round of its own while the chip waits (PR 38 read
~0.35 ms each on the v5e hosts). Every one is 32 bits an element, or a
bool, so they ride ONE int32 array ``staged`` ``[rows, cols]`` instead, each
operand a static run of columns: float32 and uint32 operands as their bits,
a bool as 0/1. The host fills writable views of it (``StagedLayout.views``)
and transfers it once; the program's first lines split it back into the
same operands with the same dtypes (``StagedLayout.split``), bit for bit.

The column layout is a function of shapes the engine already has
(``paged_layouts``): the chunk or the speculative width, the block-table
width (two groups for a window family), the key's words, whether rows carry
state, whether an adapter registry is attached. No option of its own.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
from jax import lax

_WORD = np.dtype(np.int32)


@dataclasses.dataclass(frozen=True)
class Field:
    """One operand: ``width`` None is a ``[rows]`` vector in one column,
    else ``[rows, width]``; ``default`` is what a row nothing writes holds
    (a free lane, a group's padding)."""

    name: str
    width: int | None
    dtype: np.dtype
    default: float | int | bool = 0


class StagedLayout:
    """The columns of one program kind's ``staged`` array, in order."""

    def __init__(self, fields) -> None:
        self.fields = tuple(
            dataclasses.replace(f, dtype=np.dtype(f.dtype)) for f in fields)
        self._start: dict[str, int] = {}
        col = 0
        for f in self.fields:
            if f.dtype != np.bool_ and f.dtype.itemsize != _WORD.itemsize:
                raise ValueError(
                    f"staged operand {f.name!r} is {f.dtype}: only 32-bit "
                    "operands and bools ride the int32 staged array")
            self._start[f.name] = col
            col += f.width or 1
        self.cols = col
        self._templates: dict[int, np.ndarray] = {}

    def _columns(self, staged, f: Field):
        c = self._start[f.name]
        return staged[:, c] if f.width is None else staged[:, c:c + f.width]

    def new(self, rows: int) -> np.ndarray:
        """A fresh host ``[rows, cols]`` int32 array holding every operand's
        default (one copy of a template made once per row count)."""
        t = self._templates.get(rows)
        if t is None:
            t = np.zeros((rows, self.cols), np.int32)
            views = self.views(t)
            for f in self.fields:
                views[f.name][...] = f.default
            self._templates[rows] = t
        return t.copy()

    def views(self, staged: np.ndarray) -> dict[str, np.ndarray]:
        """Writable views of ``staged``, one an operand, each in its
        operand's dtype (a bool's is the int32 column: it takes 0/1)."""
        out = {}
        for f in self.fields:
            x = self._columns(staged, f)
            if f.dtype not in (_WORD, np.bool_):
                x = x.view(f.dtype)
            out[f.name] = x
        return out

    def split(self, staged: jax.Array) -> dict[str, jax.Array]:
        """Inside a program: ``staged`` back into its operands, bit for
        bit what the host wrote in each one's dtype."""
        out = {}
        for f in self.fields:
            x = self._columns(staged, f)
            if f.dtype == np.bool_:
                x = x != 0
            elif f.dtype != _WORD:
                x = lax.bitcast_convert_type(x, f.dtype)
            out[f.name] = x
        return out


def paged_layouts(*, chunk: int, spec_width: int, table_width: int,
                  key_words: int, vocab: int, scratch_row: int | None,
                  tenants: bool) -> dict[str, StagedLayout]:
    """The three paged program kinds' layouts. ``scratch_row``: the state
    row a prefill group's padding points at where rows carry state (None:
    they do not, and there is no ``slot_rows`` operand); ``tenants``: an
    adapter registry is attached (a ``tenants`` operand, the rows' slots).
    The defaults: a lane nothing writes is greedy (so it never asks the
    sampler for the full-vocabulary sort, ``decode.needs_full_sort``), no
    top-k (``vocab``), top-p off (2.0), table 0 (the scratch page)."""

    sampling = (
        Field("greedy", None, np.bool_, True),
        Field("t", None, np.float32, 1.0),
        Field("k", None, np.int32, vocab),
        Field("p", None, np.float32, 2.0),
        Field("keydata", key_words, np.uint32),
    )

    lora = (Field("tenants", None, np.int32),) if tenants else ()
    step = (
        Field("pos", None, np.int32),
        Field("tables", table_width, np.int32),
        Field("folds", None, np.int32),
    )
    return {
        "prefill": StagedLayout((
            Field("chunks", chunk, np.int32),
            Field("valid", None, np.int32, 1),
            Field("start", None, np.int32),
            Field("tables", table_width, np.int32),
            *sampling,
            *(() if scratch_row is None
              else (Field("slot_rows", None, np.int32, scratch_row),)),
            *lora,
        )),
        "decode_step": StagedLayout((
            Field("toks", None, np.int32), *step, *sampling, *lora,
        )),
        "decode_spec_step": StagedLayout((
            Field("toks", spec_width, np.int32), *step, *sampling,
            Field("n_draft", None, np.int32), *lora,
        )),
    }
