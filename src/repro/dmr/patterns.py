"""Named redistribution patterns — the paper's Table-1 family as a registry.

The paper predefines a family of ``DMR_Send/Recv_*`` communication patterns
(default 1-D blocks, block-cyclic, custom) that user code selects by *name*
instead of hand-writing the transfer.  This module is that selection surface
for JAX jobs:

* ``get_pattern("default")`` / ``"blockcyclic:<block>"`` / ``"replicate"``
  resolve registry names to :class:`Pattern` objects; ``register_pattern``
  adds project-specific ones.
* A pattern operates at two levels that share one accounting model:

  - **device level** (the runner's resize path): ``apply(leaves, shardings,
    ctx)`` moves a group of pytree leaves onto their new shardings and
    returns the moved leaves plus a :class:`TransferStats`;
  - **host level** (Table-1 semantics, tests, benchmarks):
    ``host_redistribute(parts, new_nprocs)`` maps per-rank numpy blocks from
    the old worker count to the new one.

* ``redistribute_tree`` composes patterns over one state pytree: each
  subtree (selected by path prefix, e.g. ``{"table": "replicate"}``) goes
  through its own pattern, and the result carries both an aggregate and a
  per-pattern ``TransferStats`` breakdown.

Accounting: ``default`` reports the full resident bytes of what it moved
(the paper's §3.2 observation — cost is dominated by state size);
``blockcyclic`` reports the *communication volume* of the layout change
(bytes in blocks whose owner rank changes, zero for a no-op resize);
``replicate`` reports the broadcast payload (bytes × new worker count).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import jax
import numpy as np

from repro.core.redistribute import (TransferStats, blockcyclic_redistribute,
                                     default_redistribution)
from repro.core.spans import span

PatternSpec = Union[str, "Pattern", Callable]


@dataclasses.dataclass(frozen=True)
class ResizeContext:
    """What a pattern may know about the resize it is serving."""
    from_procs: int
    to_procs: int
    donate: bool = True


def _leaf_nbytes(leaf) -> int:
    return leaf.size * leaf.dtype.itemsize


def _uniform_owner(n_rows: int, nprocs: int) -> np.ndarray:
    """Owner rank of each row under a balanced contiguous 1-D distribution."""
    return (np.arange(n_rows) * nprocs) // n_rows


class Pattern:
    """One named redistribution pattern (device + host level)."""

    name = "pattern"

    def spec(self) -> str:
        """The registry string that reproduces this pattern."""
        return self.name

    # -- device level (the runner's resize path) -----------------------
    def leaf_bytes(self, leaf, ctx: ResizeContext) -> int:
        """Accounted bytes for moving one leaf (pattern-specific model)."""
        return _leaf_nbytes(leaf)

    def apply(self, leaves: List, shardings: List,
              ctx: ResizeContext) -> Tuple[List, TransferStats]:
        """Move a group of leaves onto their new shardings."""
        t0 = time.perf_counter()
        moved = jax.device_put(leaves, list(shardings), donate=ctx.donate,
                               may_alias=not ctx.donate)
        jax.block_until_ready(moved)
        dt = time.perf_counter() - t0
        nbytes = sum(self.leaf_bytes(l, ctx) for l in moved)
        return list(moved), TransferStats(bytes_moved=int(nbytes), seconds=dt,
                                          n_leaves=len(moved))

    # -- host level (Table-1 per-rank semantics) -----------------------
    def host_redistribute(self, parts: List[np.ndarray],
                          new_nprocs: int) -> Tuple[List[np.ndarray],
                                                    TransferStats]:
        raise NotImplementedError(
            f"pattern {self.spec()!r} has no host-level redistribution")

    def __repr__(self):
        return f"{type(self).__name__}({self.spec()!r})"


class DefaultPattern(Pattern):
    """Default Redistribution (paper Fig. 2): 1-D uniform contiguous blocks.

    Device level: the leaves are re-put onto the new shardings and the full
    resident bytes are accounted.  Host level: ``default_redistribution``
    with communication-volume accounting (rows whose owner rank changes).
    """

    name = "default"

    def host_redistribute(self, parts, new_nprocs):
        t0 = time.perf_counter()
        out = default_redistribution(list(parts), new_nprocs)
        dt = time.perf_counter() - t0
        old_sizes = [p.shape[0] for p in parts]
        new_sizes = [p.shape[0] for p in out]
        old_owner = np.repeat(np.arange(len(parts)), old_sizes)
        new_owner = np.repeat(np.arange(new_nprocs), new_sizes)
        row_bytes = parts[0].itemsize * int(np.prod(parts[0].shape[1:],
                                                    dtype=np.int64)) \
            if parts else 0
        moved = int(np.count_nonzero(old_owner != new_owner)) * row_bytes
        return out, TransferStats(bytes_moved=moved, seconds=dt,
                                  n_leaves=len(out))


class BlockCyclicPattern(Pattern):
    """Block-Cyclic Redistribution (paper Table 1, second group).

    ``blockcyclic:<block>`` repartitions at ``block``-row granularity with
    owners assigned round-robin.  Accounting (both levels) is the layout
    change's communication volume: bytes in blocks whose owner rank changes
    between the old and new round-robin maps — zero when the worker count
    is unchanged.
    """

    name = "blockcyclic"

    def __init__(self, block: int = 1):
        assert block >= 1, block
        self.block = int(block)

    def spec(self) -> str:
        return f"{self.name}:{self.block}"

    def _moved_rows(self, n_rows: int, ctx: ResizeContext) -> int:
        if ctx.from_procs == ctx.to_procs or n_rows == 0 or \
                not ctx.from_procs or not ctx.to_procs:
            return 0
        blocks = np.arange((n_rows + self.block - 1) // self.block)
        changed = (blocks % ctx.from_procs) != (blocks % ctx.to_procs)
        rows = np.full(blocks.shape, self.block, dtype=np.int64)
        rem = n_rows - (len(blocks) - 1) * self.block
        rows[-1] = rem                         # trailing partial block
        return int(rows[changed].sum())

    def leaf_bytes(self, leaf, ctx: ResizeContext) -> int:
        if leaf.ndim == 0:
            return 0
        n_rows = leaf.shape[0]
        row_bytes = _leaf_nbytes(leaf) // max(n_rows, 1)
        return self._moved_rows(n_rows, ctx) * row_bytes

    def host_redistribute(self, parts, new_nprocs):
        t0 = time.perf_counter()
        out = blockcyclic_redistribute(list(parts), new_nprocs, self.block)
        dt = time.perf_counter() - t0
        n_rows = sum(p.shape[0] for p in parts)
        row_bytes = parts[0].itemsize * int(np.prod(parts[0].shape[1:],
                                                    dtype=np.int64)) \
            if parts else 0
        ctx = ResizeContext(len(parts), new_nprocs)
        moved = self._moved_rows(n_rows, ctx) * row_bytes
        return out, TransferStats(bytes_moved=moved, seconds=dt,
                                  n_leaves=len(out))


class ReplicatePattern(Pattern):
    """Re-replication (the HPG-aligner reference table): every worker in the
    new allocation receives a full copy; accounted as the broadcast payload
    (leaf bytes × new worker count)."""

    name = "replicate"

    def leaf_bytes(self, leaf, ctx: ResizeContext) -> int:
        return _leaf_nbytes(leaf) * max(ctx.to_procs, 1)

    def host_redistribute(self, parts, new_nprocs):
        t0 = time.perf_counter()
        src = parts[0]
        out = [src.copy() for _ in range(new_nprocs)]
        dt = time.perf_counter() - t0
        return out, TransferStats(bytes_moved=src.nbytes * new_nprocs,
                                  seconds=dt, n_leaves=new_nprocs)


class CallablePattern(Pattern):
    """Adapter for a user function ``fn(leaf, new_sharding, ctx) -> leaf``
    (the paper's user-supplied send/recv functions, leaf-at-a-time)."""

    name = "custom"

    def __init__(self, fn: Callable, name: Optional[str] = None):
        self.fn = fn
        if name:
            self.name = name
        elif getattr(fn, "__name__", None) not in (None, "<lambda>"):
            self.name = f"custom:{fn.__name__}"

    def apply(self, leaves, shardings, ctx):
        t0 = time.perf_counter()
        moved = [self.fn(l, s, ctx) for l, s in zip(leaves, shardings)]
        jax.block_until_ready(moved)
        dt = time.perf_counter() - t0
        nbytes = sum(_leaf_nbytes(l) for l in moved)
        return moved, TransferStats(bytes_moved=int(nbytes), seconds=dt,
                                    n_leaves=len(moved))


# ----------------------------------------------------------------------
# Registry
# ----------------------------------------------------------------------

#: name -> factory(arg: str|None) -> Pattern
PATTERNS: Dict[str, Callable[[Optional[str]], Pattern]] = {
    "default": lambda arg: DefaultPattern(),
    "replicate": lambda arg: ReplicatePattern(),
    "blockcyclic": lambda arg: BlockCyclicPattern(int(arg or 1)),
}


def register_pattern(name: str,
                     factory: Callable[[Optional[str]], Pattern]) -> None:
    """Register a custom pattern family under ``name`` (``factory`` receives
    the text after ``name:`` in the spec, or ``None``)."""
    if ":" in name:
        raise ValueError(f"pattern name must not contain ':': {name!r}")
    PATTERNS[name] = factory


def get_pattern(spec: PatternSpec) -> Pattern:
    """Resolve a pattern spec: a Pattern instance, a registry name such as
    ``"default"`` / ``"blockcyclic:4"`` / ``"replicate"``, or a callable
    ``fn(leaf, new_sharding, ctx) -> leaf``."""
    if isinstance(spec, Pattern):
        return spec
    if callable(spec):
        return CallablePattern(spec)
    name, _, arg = str(spec).partition(":")
    try:
        factory = PATTERNS[name]
    except KeyError:
        raise KeyError(f"unknown redistribution pattern {spec!r}; "
                       f"known: {sorted(PATTERNS)}")
    return factory(arg or None)


# ----------------------------------------------------------------------
# Per-subtree composition over a state pytree
# ----------------------------------------------------------------------

def _key_str(k) -> str:
    for attr in ("key", "idx", "name"):
        if hasattr(k, attr):
            return str(getattr(k, attr))
    return str(k)


def _path_str(path) -> str:
    return "/".join(_key_str(k) for k in path)


def _match_spec(path: str, patterns: Dict[str, PatternSpec],
                default: PatternSpec) -> PatternSpec:
    """Longest path-prefix match; ``"*"`` overrides the default."""
    best, best_len = None, -1
    for key, spec in patterns.items():
        if key == "*":
            continue
        if (path == key or path.startswith(key + "/")) and len(key) > best_len:
            best, best_len = spec, len(key)
    if best_len >= 0:
        return best
    return patterns.get("*", default)


def redistribute_tree(state, new_shardings, *,
                      patterns: Optional[Dict[str, PatternSpec]] = None,
                      default: PatternSpec = "default",
                      from_procs: int = 0, to_procs: int = 0,
                      donate: bool = True
                      ) -> Tuple[Any, TransferStats,
                                 Dict[str, TransferStats]]:
    """Move a state pytree onto new shardings, pattern-by-pattern.

    ``patterns`` maps path prefixes (``"table"``, ``"opt/mu"``, ``"*"``) to
    pattern specs; unmatched subtrees use ``default``.  Returns
    ``(new_state, aggregate_stats, per_pattern_stats)`` where the breakdown
    is keyed by each pattern's ``spec()`` string.
    """
    ctx = ResizeContext(from_procs=from_procs, to_procs=to_procs,
                        donate=donate)
    paths_leaves, treedef = jax.tree_util.tree_flatten_with_path(state)
    shard_leaves = treedef.flatten_up_to(new_shardings)
    patterns = patterns or {}

    resolved: Dict[Any, Pattern] = {}      # spec value/id -> Pattern (dedup)
    groups: Dict[int, List[int]] = {}      # id(pattern) -> leaf indices
    by_id: Dict[int, Pattern] = {}
    for i, (path, _leaf) in enumerate(paths_leaves):
        spec = _match_spec(_path_str(path), patterns, default)
        # dedup string specs by value, everything else (callables, Pattern
        # instances) by identity; group by *pattern* identity so two
        # distinct callables stay distinct even if their spec() strings
        # collide (e.g. two lambdas, both "custom")
        key = spec if isinstance(spec, str) else id(spec)
        pat = resolved.get(key)
        if pat is None:
            pat = resolved[key] = get_pattern(spec)
        by_id[id(pat)] = pat
        groups.setdefault(id(pat), []).append(i)

    out_leaves: List = [None] * len(paths_leaves)
    per_pattern: Dict[str, TransferStats] = {}
    for pat_id, idxs in groups.items():
        pat = by_id[pat_id]
        with span("dmr.redistribute", pattern=pat.spec()):
            moved, stats = pat.apply([paths_leaves[i][1] for i in idxs],
                                     [shard_leaves[i] for i in idxs], ctx)
        for i, leaf in zip(idxs, moved):
            out_leaves[i] = leaf
        key, n = pat.spec(), 2
        while key in per_pattern:          # spec-string collision: suffix
            key, n = f"{pat.spec()}#{n}", n + 1
        per_pattern[key] = stats

    total = TransferStats(
        bytes_moved=sum(s.bytes_moved for s in per_pattern.values()),
        seconds=sum(s.seconds for s in per_pattern.values()),
        n_leaves=sum(s.n_leaves for s in per_pattern.values()))
    return treedef.unflatten(out_leaves), total, per_pattern
