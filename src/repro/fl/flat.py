"""Model parameters carried as one flat device buffer per dtype.

A jitted call pays host time for every buffer it takes and returns. A
ResNet56 tree is 169 leaves, so a local SGD step over the tree hands the
runtime about 340 buffers per launch; over a ``FlatParams`` it hands it
the model's one float32 buffer, the batch and the loss.

``FlatParams`` is the carrier: the buffers plus their ``Layout`` (the
tree's structure and, per leaf, shape, dtype, buffer and offset). It is
immutable. It is a pytree node whose leaves are the model's leaves in
``jax.tree.leaves`` order, unpacked from the buffers by one jitted call
on first use and kept, so tree code sees the same arrays as the tree
form. ``pack`` builds it from a tree; ``tree()`` gives the tree back.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class Layout:
    """Where each leaf of a tree lives in the flat buffers: leaves are
    grouped by dtype, one buffer per dtype in order of first appearance,
    and laid end to end in ``jax.tree.leaves`` order within a buffer.
    Hashable, so a jitted function can take it as a static argument."""

    __slots__ = ("treedef", "shapes", "dtypes", "slots", "sizes", "_key",
                 "_hash")

    def __init__(self, treedef, shapes: Sequence[tuple], dtypes: Sequence):
        self.treedef = treedef
        self.shapes = tuple(tuple(s) for s in shapes)
        self.dtypes = tuple(np.dtype(d) for d in dtypes)
        order = list(dict.fromkeys(self.dtypes))
        fill = [0] * len(order)
        slots = []
        for shape, dtype in zip(self.shapes, self.dtypes):
            b = order.index(dtype)
            n = int(np.prod(shape, dtype=np.int64))
            slots.append((b, fill[b], n))
            fill[b] += n
        self.slots = tuple(slots)  # (buffer, offset, size) per leaf
        self.sizes = tuple(fill)  # elements per buffer
        self._key = (treedef, self.shapes, self.dtypes)
        self._hash = hash(self._key)

    @classmethod
    def of(cls, tree) -> Tuple["Layout", list]:
        """-> (the layout of ``tree``, its leaves). Leaf dtypes are the
        ones JAX gives them on the device (float64 is float32 unless
        64-bit mode is on)."""
        leaves, treedef = jax.tree.flatten(tree)
        dtypes = [jax.dtypes.canonicalize_dtype(np.result_type(x))
                  for x in leaves]
        return cls(treedef, [np.shape(x) for x in leaves], dtypes), leaves

    def __hash__(self):
        return self._hash

    def __eq__(self, other):
        return self is other or (isinstance(other, Layout)
                                 and self._key == other._key)

    def split(self, buffers) -> list:
        """The leaves, sliced and reshaped out of ``buffers`` (traceable)."""
        return [buffers[b][o:o + n].reshape(s)
                for (b, o, n), s in zip(self.slots, self.shapes)]

    def join(self, leaves, xp=jnp) -> tuple:
        """The buffers, concatenated from ``leaves`` in layout order
        (traceable with ``jnp``; on the host with ``np``)."""
        groups = [[] for _ in self.sizes]
        for (b, _, _), x, dtype in zip(self.slots, leaves, self.dtypes):
            groups[b].append(xp.ravel(xp.asarray(x, dtype)))
        return tuple(xp.concatenate(g) for g in groups)

    def unflatten(self, buffers):
        """The tree, built from ``buffers`` (traceable)."""
        return jax.tree.unflatten(self.treedef, self.split(buffers))

    def flatten(self, tree) -> tuple:
        """The buffers of a tree of this layout (traceable)."""
        return self.join(jax.tree.leaves(tree))


@functools.partial(jax.jit, static_argnums=0)
def flat_pack(layout: Layout, leaves):
    return layout.join(leaves)


@functools.partial(jax.jit, static_argnums=0)
def flat_unpack(layout: Layout, buffers):
    return layout.split(buffers)


class FlatParams:
    """A tree's leaves as one flat buffer per dtype (see module doc).

    Built from buffers (``pack``, a step's output) or, by the pytree
    machinery, from leaves; the other form is made on first use."""

    __slots__ = ("layout", "_buffers", "_leaves")

    def __init__(self, layout: Layout, buffers: Optional[tuple] = None,
                 leaves: Optional[list] = None):
        if buffers is None and leaves is None:
            raise ValueError("FlatParams needs its buffers or its leaves")
        self.layout = layout
        self._buffers = None if buffers is None else tuple(buffers)
        self._leaves = None if leaves is None else list(leaves)

    @classmethod
    def pack(cls, tree) -> "FlatParams":
        """The carrier of ``tree``. Host leaves are concatenated on the
        host and uploaded once per buffer; device leaves are joined by one
        jitted call."""
        layout, leaves = Layout.of(tree)
        if leaves and not any(isinstance(x, jax.Array) for x in leaves):
            buffers = tuple(jax.device_put(b)
                            for b in layout.join(leaves, xp=np))
        else:
            buffers = flat_pack(layout, leaves)
        return cls(layout, buffers=buffers)

    @property
    def buffers(self) -> tuple:
        if self._buffers is None:
            self._buffers = flat_pack(self.layout, self._leaves)
        return self._buffers

    def leaves(self) -> list:
        """The tree's leaves, in ``jax.tree.leaves`` order."""
        if self._leaves is None:
            self._leaves = flat_unpack(self.layout, self._buffers)
        return list(self._leaves)

    def tree(self):
        """The tree this carrier holds."""
        return jax.tree.unflatten(self.layout.treedef, self.leaves())


jax.tree_util.register_pytree_node(
    FlatParams,
    lambda c: (c.leaves(), c.layout),
    lambda layout, leaves: FlatParams(layout, leaves=leaves))
