"""A token table that a plain SGD step updates by the rows it looked up.

A lookup reads ``[positions]`` rows of a ``[V, C]`` table; its gradient is
those rows' cotangent scattered into a zeroed ``[V, C]`` array, and the
step ``p - lr * g`` then writes the whole table. At a language model's
vocabulary (GPT-2: 50,257 x 2,048, 4,096 positions a step) that is a
cast of the whole table to the compute dtype, a dense scatter-add and a
dense SGD-and-select a step, for 4,096 rows that changed.

:class:`RowEmbed` is ``nn.Embed`` (the same parameter, ``embedding``, the
same initialiser and values from a seed). Under the client-update loop's
row step (``parallel/engine.py`` ``_make_trip_loop_core``) its lookup
gathers the rows from the float32 table and casts the rows, not the
table (the same values bit for bit), and talks to the step through one
flax collection, :data:`ROW_STEP`:

- it records the ids it looked up (``ids``, a tuple: one entry a call);
- where the step gave it ``delta`` (zeros ``ids.shape + (C,)``, float32),
  it reads the table without a gradient and adds ``delta`` to the rows,
  so differentiating by ``delta`` gives the rows' cotangent ``[positions,
  C]`` and no table-shaped gradient exists;
- :func:`step_rows` then applies ``table.at[ids].add(-lr * ct)`` in place:
  plain SGD, duplicate ids summed (in float32), every row not looked up
  untouched.

Without that collection (or where it is not mutable: evaluation, every
other update variant, initialisation) the lookup is ``nn.Embed``'s and
differentiates as it always did. The table is read by the lookup alone:
``attend`` (a tied head) is refused.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax import traverse_util
from jax import custom_batching

#: the collection through which a lookup and the row step talk
ROW_STEP = "row_step"
#: the table's parameter name under the module (``nn.Embed``'s)
TABLE = "embedding"


class RowEmbed(nn.Embed):
    """``nn.Embed`` whose table a plain SGD step can update by rows
    (module docstring)."""

    def __call__(self, inputs):
        if self.is_initializing() or not self.is_mutable_collection(ROW_STEP):
            return super().__call__(inputs)
        seen = self.get_variable(ROW_STEP, "ids", ())
        self.put_variable(ROW_STEP, "ids", seen + (inputs,))
        if not self.has_variable(ROW_STEP, "delta"):
            return super().__call__(inputs)
        rows = take_rows(jax.lax.stop_gradient(self.embedding), inputs)
        rows = rows + self.get_variable(ROW_STEP, "delta")
        return rows if self.dtype is None else rows.astype(self.dtype)

    def attend(self, query):
        raise TypeError(f"{self.name}: a RowEmbed table is read by its lookup "
                        "alone (a tied head would take a gradient the row "
                        "step does not apply)")


@custom_batching.custom_vmap
def take_rows(table, ids):
    """``jnp.take(table, ids, axis=0)``, whose ``vmap`` over a lane axis of
    one takes that axis off first. XLA's batched gather over a ``[1, V,
    C]`` table asks for the table in another tiling: on a TPU v5e a copy
    of the whole table a step (cast to bf16 where the rows are cast next),
    which the plain gather over ``[V, C]`` does not make (PERF.md, PR
    38). Other lane counts batch as ``jnp.take`` does."""
    return jnp.take(table, ids, axis=0)


@take_rows.def_vmap
def _take_rows_vmap(axis_size, in_batched, table, ids):
    table_batched, ids_batched = in_batched
    if axis_size == 1 and table_batched:
        return take_rows(table[0], ids[0] if ids_batched else ids)[None], True
    axes = (0 if table_batched else None, 0 if ids_batched else None)
    return jax.vmap(lambda t, i: jnp.take(t, i, axis=0),
                    in_axes=axes)(table, ids), True


def lookups(collection):
    """``{module path: ids tuple}`` of a mutated :data:`ROW_STEP`
    collection (one ids array a call of that module)."""
    flat = traverse_util.flatten_dict(collection)
    return {k[:-1]: v for k, v in flat.items() if k[-1] == "ids"}


def plan_rows(params, found):
    """The tables a step updates by rows: ``{module path: ids}`` of every
    lookup of ``found`` (:func:`lookups`, abstract) that is called once a
    step and reads fewer positions than its table holds; a table read
    whole (GPT-2's ``pos_embed``: 2,048 of 2,048 rows) stays dense."""
    flat = traverse_util.flatten_dict(params)
    return {path: ids[0] for path, ids in found.items()
            if len(ids) == 1 and ids[0].size < flat[path + (TABLE,)].shape[0]}


def split_tables(params, rows):
    """``(tables, rest)``: the row-stepped tables ``{module path: table}``
    and the parameter tree without them."""
    if not rows:
        return {}, params
    flat = traverse_util.flatten_dict(params)
    tables = {path: flat.pop(path + (TABLE,)) for path in rows}
    return tables, traverse_util.unflatten_dict(flat)


def join_tables(params, tables):
    """The whole parameter tree again (:func:`split_tables`' inverse)."""
    if not tables:
        return params
    flat = traverse_util.flatten_dict(params)
    flat.update({path + (TABLE,): t for path, t in tables.items()})
    return traverse_util.unflatten_dict(flat)


def zero_deltas(tables, rows):
    """The :data:`ROW_STEP` collection a row step hands the lookups:
    ``delta`` zeros of ``ids.shape + (C,)`` in the table's dtype."""
    return traverse_util.unflatten_dict({
        path + ("delta",): jnp.zeros(ids.shape + tables[path].shape[-1:],
                                     tables[path].dtype)
        for path, ids in rows.items()})


def step_rows(tables, collection, rows_ct, valid, lr):
    """``table.at[ids].add(-lr * where(valid, ct, 0))`` for every
    row-stepped table: the SGD step on the rows looked up, ids from the
    lookup itself (``collection``, the mutated :data:`ROW_STEP`), the
    rows' cotangent by ``delta`` (``rows_ct``). A step with ``valid``
    false adds zeros and changes nothing."""
    found = lookups(collection)
    ct = traverse_util.flatten_dict(rows_ct)
    out = {}
    for path, table in tables.items():
        ids = found[path][0].reshape(-1)
        g = jnp.where(valid, ct[path + ("delta",)], 0)
        out[path] = table.at[ids].add(
            (-lr * g).reshape(ids.shape + table.shape[-1:]))
    return out


__all__ = ["ROW_STEP", "TABLE", "RowEmbed", "take_rows", "lookups", "plan_rows",
           "split_tables", "join_tables", "zero_deltas", "step_rows"]
