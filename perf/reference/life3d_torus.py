"""Plain 3-D Life on a 3-torus: the reference for every volume cell.

The rule is totalistic over the 26 cells of the 3x3x3 Moore
neighbourhood, given as ``B<counts>/S<counts>`` (Bays 4555 is
``B5/S4,5``: C. Bays, "Candidates for the Game of Life in Three
Dimensions", Complex Systems 1, 1987).  ``step_dense`` states it cell by
cell on a uint8 ``[D, H, W]`` volume: the 27-cell sum from separable
rolls (three cells along the plane axis, then along rows, then along
columns), less the cell itself, then the birth and survival sets.  All
three axes wrap.

``wrap_planes=False`` is the control: the wrap of the plane axis is left
out, so the first and last planes see dead ghost planes, as a ring of
plane slabs with its exchange left out would.  It breaks the
configuration's stated guarantee (all three axes wrap).
"""

from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp

_RULE = re.compile(r"^B([\d,]*)/S([\d,]*)$", re.IGNORECASE)


def parse_rule(text: str) -> tuple:
    """``"B5/S4,5"`` -> ``(frozenset({5}), frozenset({4, 5}))``."""
    m = _RULE.match(text.strip())
    if not m:
        raise ValueError(f"not a B<counts>/S<counts> rule: {text!r}")
    return tuple(frozenset(int(c) for c in g.split(",") if c) for g in m.groups())


def _planes(v, shift: int, wrap_planes: bool):
    """The volume moved ``shift`` planes along axis 0 (+1 or -1)."""
    if wrap_planes:
        return jnp.roll(v, shift, 0)
    zero = jnp.zeros_like(v[:1])
    if shift == 1:
        return jnp.concatenate([zero, v[:-1]], axis=0)
    return jnp.concatenate([v[1:], zero], axis=0)


def _member(n, counts):
    out = jnp.zeros(n.shape, jnp.bool_)
    for c in sorted(counts):
        out = out | (n == c)
    return out


def step_dense(v, rule: str = "B5/S4,5", wrap_planes: bool = True):
    """One generation of a ``[D, H, W]`` uint8 volume of 0s and 1s."""
    birth, survive = parse_rule(rule)
    s = v + _planes(v, 1, wrap_planes) + _planes(v, -1, wrap_planes)
    s = s + jnp.roll(s, 1, 1) + jnp.roll(s, -1, 1)
    s = s + jnp.roll(s, 1, 2) + jnp.roll(s, -1, 2)
    n = s - v  # the 26 neighbours: at most 26, so uint8 holds every sum
    alive = v == 1
    return jnp.where(alive, _member(n, survive), _member(n, birth)).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("generations", "rule", "wrap_planes"))
def evolve(volume, generations: int, rule: str = "B5/S4,5", wrap_planes: bool = True):
    """``generations`` steps of a ``[D, H, W]`` uint8 volume."""
    return jax.lax.fori_loop(
        0, generations, lambda _, v: step_dense(v, rule, wrap_planes), volume
    )


def mismatches(got, want) -> int:
    """Cells that differ, over the whole volume."""
    return int(jnp.sum(got != want, dtype=jnp.int32))
