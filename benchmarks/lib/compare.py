"""The comparison that decides ``correct`` for a training cell.

Numbers compared, each with a limit of its own (the workload file's
``"limits"``; PERF.md gives the readings each was set from):

- ``loss_gap``: the worst relative gap of a training loss, over every
  worker's first ``steps`` steps, between the program and the reference;
- ``grad_norm_gap``: per leaf, the gap between the norm of the first
  gradient as the program's optimizer got it (its first Adam moment after
  one step, over 1 - b1) and the reference's, measured against the
  reference's norm of that leaf or of the median leaf, whichever is larger;
  the worst leaf, over the workers;
- ``update_norm_gap``: the same measure on the norm of the parameters'
  change after ``steps`` steps (after the round's synchronisation, where
  there are several workers).  Leaves whose reference gradient is under a
  thousandth of the median leaf's are left out: Adam moves them by
  round-off alone (a key's bias under softmax);
- ``update_scatter_gap``: how far the large leaves' update-norm gaps lie
  from one another: the root mean square, about their median, of the signed
  gaps ``(prog - ref) / max(ref, median ref)`` over the leaves of 2**14
  elements or more that are not left out above.  Rounding noise averages
  out in a large leaf's norm and a common factor drops out with the median,
  so this is the number on which a lower precision stands apart from
  bfloat16 where the worst leaf does not (PERF.md, section 4);
- ``twin_loss_gap``: the largest difference between the losses of the
  short calls that expose the state and those of the measured call's own
  first round, on the same rows.  They run one compiled program from one
  seed, so the limit is 0.

A "leaf" is one layer's slice of a stacked leaf, and the query, key and
value parts of the fused projection count apart, so that a part that has
no gradient cannot hide in a leaf that has.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

ADAM_B1 = 0.9
DEAD_GRADIENT = 1e-3   # of the median leaf's gradient norm
LARGE_LEAF = 2**14     # elements


def _block_norms(path: tuple[str, ...], x):
    """Norms of one leaf's blocks: per layer under ``layers``, and per
    q/k/v part of a fused ``qkv`` leaf.  Returns {name: scalar}."""
    x = x.astype(jnp.float32)
    name = "/".join(path)
    stacked = path[0] == "layers"
    if not stacked:
        return {name: jnp.sqrt(jnp.sum(x * x))}
    out = {}
    if "qkv" in path:
        # kernel [L, H, 3, heads, hd], bias [L, 3, heads, hd]
        axis = 2 if path[-1] == "kernel" else 1
        x = jnp.moveaxis(x, axis, 1)               # [L, 3, ...]
        n = jnp.sqrt(jnp.sum(x * x, axis=tuple(range(2, x.ndim))))
        for j, part in enumerate("qkv"):
            for i in range(n.shape[0]):
                out[f"{name}.{part}[{i}]"] = n[i, j]
        return out
    n = jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
    for i in range(n.shape[0]):
        out[f"{name}[{i}]"] = n[i]
    return out


def _paths(tree: dict, prefix=()):
    for k in sorted(tree):
        v = tree[k]
        if isinstance(v, dict) or hasattr(v, "items"):
            yield from _paths(dict(v), prefix + (k,))
        else:
            yield prefix + (k,), v


@jax.jit
def _norms_jit(tree):
    out = {}
    for path, leaf in _paths(tree):
        out.update(_block_norms(path, leaf))
    return out


def block_norms(tree: dict) -> dict[str, float]:
    """{block name: norm} of a parameter-shaped tree (one worker's)."""
    return {k: float(v) for k, v in _norms_jit(_as_dict(tree)).items()}


def block_norms_by_worker(tree: dict, scale: float = 1.0) -> list[dict]:
    """One {block name: norm * scale} a worker, of a tree whose leaves
    carry the workers on a leading axis (the program's stacked state)."""
    stacked = jax.device_get(jax.jit(jax.vmap(_norms_jit))(_as_dict(tree)))
    workers = len(next(iter(stacked.values())))
    return [{k: float(v[w]) * scale for k, v in stacked.items()}
            for w in range(workers)]


def _as_dict(tree):
    if isinstance(tree, dict) or hasattr(tree, "items"):
        return {k: _as_dict(v) for k, v in dict(tree).items()}
    return tree


def worst_gap(prog: dict[str, float], ref: dict[str, float],
              leave_out: set[str] = frozenset()) -> tuple[float, str]:
    """Worst block by |prog - ref| / max(ref, median ref)."""
    names = [n for n in ref if n not in leave_out]
    med = float(np.median([ref[n] for n in names]))
    worst, at = 0.0, ""
    for n in names:
        gap = abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30)
        if not np.isfinite(gap):
            return float("inf"), n
        if gap > worst:
            worst, at = gap, n
    return worst, at


def scatter_gap(prog: dict[str, float], ref: dict[str, float],
                names: list[str]) -> float:
    """Root mean square, about their median, of the signed gaps of
    ``names``; the measure is ``worst_gap``'s."""
    if len(names) < 2:
        return 0.0
    med = float(np.median([ref[n] for n in names]))
    gaps = np.asarray([(prog[n] - ref[n]) / max(ref[n], med, 1e-30)
                       for n in names])
    return float(np.sqrt(np.mean((gaps - np.median(gaps)) ** 2)))


def block_sizes(tree: dict) -> dict[str, int]:
    """{block name: elements}, the names being ``block_norms``'."""
    out = {}
    for path, leaf in _paths(_as_dict(tree)):
        shape = jax.eval_shape(lambda x: _block_norms(path, x), leaf)
        for name in shape:
            out[name] = int(leaf.size) // len(shape)
    return out


def dead_blocks(ref_grad_norms: dict[str, float]) -> set[str]:
    med = float(np.median(list(ref_grad_norms.values())))
    return {n for n, v in ref_grad_norms.items() if v < DEAD_GRADIENT * med}


def tree_sub(a: dict, b: dict) -> dict:
    return jax.tree_util.tree_map(lambda x, y: jnp.asarray(x) - jnp.asarray(y),
                                  _as_dict(a), _as_dict(b))


def tree_mean(trees: list[dict]) -> dict:
    return jax.tree_util.tree_map(
        lambda *xs: sum(xs[1:], xs[0]) / len(xs), *map(_as_dict, trees))


def judge(numbers: dict[str, float], limits: dict[str, float]) -> dict:
    """{name: {"value", "limit", "ok"}} for every number compared; a value
    that is not finite is never ok."""
    out = {}
    for name, value in numbers.items():
        limit = float(limits[name])
        ok = bool(np.isfinite(value) and value <= limit)
        out[name] = {"value": float(value), "limit": limit, "ok": ok}
    return out
