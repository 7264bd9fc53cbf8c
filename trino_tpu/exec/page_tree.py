"""Page <-> flat array-list conversion (pytree-style) for jit boundaries.

The dynamic parts of a Page (values, null masks, selection, nested child
columns) flatten to a list of arrays; the static parts (types,
dictionaries, vranges) go into a PageSpec captured in the compiled
closure. Nested (array/map/row) columns flatten RECURSIVELY: the parent's
lengths/placeholder array first, then each child column — static shapes
throughout, so a traced program can ship nested results across the jit
boundary (the Block-tree serialization role of the reference's
``spi/block`` serde, re-targeted at XLA buffers).
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from trino_tpu import types as T
from trino_tpu.data.dictionary import Dictionary
from trino_tpu.data.page import Column, Page


@dataclasses.dataclass
class ColSpec:
    """Static description of one column's flat layout."""

    type: T.Type
    dictionary: Optional[Dictionary]
    has_nulls: bool
    vrange: Optional[tuple] = None
    ascending: bool = False
    has_hi: bool = False
    children: Optional[List["ColSpec"]] = None

    def count(self) -> int:
        return (1 + (1 if self.has_nulls else 0) + (1 if self.has_hi else 0)
                + sum(k.count() for k in (self.children or ())))


@dataclasses.dataclass
class PageSpec:
    col_specs: List[ColSpec]
    has_sel: bool
    live_prefix: bool = False

    # legacy accessors (older callers address columns by parallel lists)
    @property
    def types(self) -> List[T.Type]:
        return [c.type for c in self.col_specs]

    @property
    def dictionaries(self):
        return [c.dictionary for c in self.col_specs]

    @property
    def has_nulls(self):
        return [c.has_nulls for c in self.col_specs]

    @property
    def vranges(self):
        return [c.vrange for c in self.col_specs]

    def array_count(self) -> int:
        """How many flat arrays a page with this spec occupies."""
        return sum(c.count() for c in self.col_specs) + (1 if self.has_sel else 0)


def _flatten_col(c: Column, arrays: List[jnp.ndarray]) -> ColSpec:
    arrays.append(c.values)
    if c.nulls is not None:
        arrays.append(c.nulls)
    if c.hi is not None:
        arrays.append(c.hi)
    children = None
    if c.children is not None:
        children = [_flatten_col(k, arrays) for k in c.children]
    return ColSpec(
        c.type, c.dictionary, c.nulls is not None, c.vrange,
        bool(c.ascending), c.hi is not None, children,
    )


def _unflatten_col(spec: ColSpec, arrays: List[jnp.ndarray], i: int
                   ) -> Tuple[Column, int]:
    vals = arrays[i]
    i += 1
    nulls = None
    if spec.has_nulls:
        nulls = arrays[i]
        i += 1
    hi = None
    if spec.has_hi:
        hi = arrays[i]
        i += 1
    children = None
    if spec.children is not None:
        children = []
        for ks in spec.children:
            k, i = _unflatten_col(ks, arrays, i)
            children.append(k)
    return Column(spec.type, vals, nulls, spec.dictionary, spec.vrange,
                  spec.ascending, hi=hi, children=children), i


def flatten_page(page: Page) -> Tuple[List[jnp.ndarray], PageSpec]:
    arrays: List[jnp.ndarray] = []
    col_specs = [_flatten_col(c, arrays) for c in page.columns]
    if page.sel is not None:
        arrays.append(page.sel)
    return arrays, PageSpec(col_specs, page.sel is not None, page.live_prefix)


def unflatten_page(spec: PageSpec, arrays: List[jnp.ndarray]) -> Page:
    cols: List[Column] = []
    i = 0
    for cs in spec.col_specs:
        c, i = _unflatten_col(cs, arrays, i)
        cols.append(c)
    sel = arrays[i] if spec.has_sel else None
    return Page(cols, sel, live_prefix=spec.live_prefix)


# ------------------------------------------------ hashable specs (jit keys)
@dataclasses.dataclass(frozen=True)
class InputDictionary:
    """Stands in a traced program for the dictionary of its input page's
    column ``channel``: the program reads the size alone, and its caller
    puts the dictionary itself back on every output column that carries
    this (``attach_dictionaries``). So pages whose dictionaries differ in
    content share one program."""

    channel: int
    size: int

    def __len__(self) -> int:
        return self.size


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class StaticSpec:
    """What ``jax.jit`` can hash of a flat page: per column (type, has
    nulls, has hi limb, dictionary stand-in), then ``has_sel``. Value
    ranges, sort order and dictionary content stay outside. A pytree node
    without leaves, so a program can also RETURN one beside its arrays,
    with whatever else it learnt while tracing (``notes``)."""

    columns: Tuple[tuple, ...]
    has_sel: bool
    notes: tuple = ()

    def page_spec(self) -> PageSpec:
        return PageSpec(
            [ColSpec(t, d, has_nulls, has_hi=has_hi)
             for t, has_nulls, has_hi, d in self.columns], self.has_sel)


def static_spec(spec: PageSpec, notes: tuple = ()) -> Optional[StaticSpec]:
    """``spec`` as a StaticSpec, a real dictionary becoming the stand-in
    of its channel; None for a page with nested columns."""
    if any(c.children is not None for c in spec.col_specs):
        return None
    columns = []
    for channel, c in enumerate(spec.col_specs):
        d = c.dictionary
        if isinstance(d, Dictionary):
            d = InputDictionary(channel, len(d))
        columns.append((c.type, c.has_nulls, c.has_hi, d))
    return StaticSpec(tuple(columns), spec.has_sel, notes)


def attach_dictionaries(out: Page, source: Page) -> Page:
    """``out``, a traced program's output page, with each InputDictionary
    replaced by the dictionary of ``source``'s column it stands for."""
    columns = [
        dataclasses.replace(
            c, dictionary=source.columns[c.dictionary.channel].dictionary)
        if isinstance(c.dictionary, InputDictionary) else c
        for c in out.columns]
    return Page(columns, out.sel, source.replicated)
