"""Element coloring for conflict-free assembly (docs/DESIGN.md §5).

FEM assembly is the same scatter race the paper's SpMV scatter term has
(Chejanovsky et al., arXiv:2012.00585): every element adds a dense
``edof × edof`` block into the global matrix, and two elements sharing a
node write the same diagonal entry (and, sharing two nodes, the same
off-diagonal slots).  So two elements conflict exactly when they share a
node — one level, no distance-2 closure needed: sharing any node already
collides on that node's diagonal, and every off-diagonal collision
requires sharing both endpoints.  (DOF interleaving is per node, so
sharing a node and sharing a DOF are the same relation for any
``ndof_per_node``.)

The ``greedy`` provider colors straight from the node–element incidence
(a CSR of elements by node, one argsort of ``conn.ravel()``), never
building the element graph, whose ~65 neighbours an element on a tet
mesh make it the size of the problem many times over:

  rounds    Jones–Plassmann rounds.  An uncolored element is chosen when
            it has the highest priority among the uncolored elements at
            each of its nodes; it takes the smallest color unused at its
            nodes, read from per-node used-color bitmasks.  Elements
            chosen in one round share no node, so their bitmask updates
            never collide.  Priorities are the element order for up to
            ``ORDERED_MAX_ROUNDS`` rounds — the result is then exactly
            first-fit in element order, which on a structured mesh takes
            O(mesh width) rounds — and seeded random ones for whatever is
            left (a mesh numbered so that its order chains for longer).
  balance   rounds of moves from larger classes into smaller ones that no
            element of the destination touches (``_balance``).

Within a color no two elements share a DOF, so the per-color scatter-add
is a permutation write — conflict-free on a machine without atomics,
exactly how the colorful SpMV path executes (§3.2).  The ``race``
provider (RACE's recursive level groups, ``core/coloring.py``) still
colors the explicit element graph (:func:`element_adjacency`).
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np

from repro import obs
from repro.core.coloring import PROVIDERS, Coloring, _finalize, color_graph

# Jones–Plassmann rounds that take the element order as the priority
# before the rest switch to seeded random priorities
ORDERED_MAX_ROUNDS = 4096
COLORING_SEED = 0x5EED
BALANCE_MAX_ROUNDS = 8

_ONE = np.uint64(1)


def element_dofs(conn: np.ndarray, ndof_per_node: int = 1) -> np.ndarray:
    """(ne, nen·d) global DOF ids per element; node v owns dofs
    [v·d, (v+1)·d) — the interleaved elasticity layout."""
    conn = np.asarray(conn)
    if ndof_per_node == 1:
        return conn.astype(np.int32)
    d = ndof_per_node
    return (conn[:, :, None].astype(np.int64) * d
            + np.arange(d)[None, None, :]).reshape(conn.shape[0], -1).astype(
                np.int32)


def element_adjacency(conn: np.ndarray) -> List[np.ndarray]:
    """Adjacency lists of the element conflict graph (e ~ f when the
    elements share at least one node), for the ``race`` provider."""
    conn = np.asarray(conn)
    ne, _ = conn.shape
    num_nodes = int(conn.max()) + 1 if conn.size else 0
    node_els: List[List[int]] = [[] for _ in range(num_nodes)]
    for e in range(ne):
        for v in conn[e]:
            node_els[int(v)].append(e)
    adj: List[List[int]] = [[] for _ in range(ne)]
    for els in node_els:
        for a in els:
            for b in els:
                if a != b:
                    adj[a].append(b)
    return [np.unique(np.asarray(a, dtype=np.int64)) for a in adj]


def node_incidence(conn: np.ndarray,
                   num_nodes: int) -> Tuple[np.ndarray, np.ndarray]:
    """``(ptr, elements)``: the elements at node v are
    ``elements[ptr[v]:ptr[v+1]]``, ascending (one stable argsort of
    ``conn.ravel()``; an element listing a node twice appears twice)."""
    conn = np.asarray(conn)
    flat = conn.ravel()
    order = np.argsort(flat, kind="stable")
    ptr = np.zeros(num_nodes + 1, np.int64)
    np.cumsum(np.bincount(flat, minlength=num_nodes), out=ptr[1:])
    return ptr, (order // conn.shape[1]).astype(np.int64)


# ---------------------------------------------------------------------------
# Per-node used-color bitmasks: (num_nodes, words) uint64, color c is bit
# c % 64 of word c // 64
# ---------------------------------------------------------------------------

def _forbidden(used: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """(len(rows), words): the colors used at any node of each row."""
    return np.bitwise_or.reduce(used[rows], axis=1)


def _first_free(used: np.ndarray,
                rows: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """The smallest color unused at the nodes of each row, and ``used``
    (a word wider when some row found every bit taken)."""
    while True:
        free = ~_forbidden(used, rows)
        word = np.argmax(free != 0, axis=1)
        fw = free[np.arange(free.shape[0]), word]
        if (fw != 0).all():
            break
        used = np.concatenate(
            [used, np.zeros((used.shape[0], 1), np.uint64)], axis=1)
    low = fw & (~fw + _ONE)                   # lowest set bit: a power of 2
    return word * 64 + np.log2(low.astype(np.float64)).astype(np.int64), used


def _set(used: np.ndarray, rows: np.ndarray, colors: np.ndarray):
    """Mark each row's color at its nodes.  Rows must share no node (a
    repeated index would keep only one write)."""
    c = np.repeat(colors, rows.shape[1])
    used[rows.ravel(), c // 64] |= _ONE << (c % 64).astype(np.uint64)


def _clear(used: np.ndarray, rows: np.ndarray, colors: np.ndarray):
    """Unmark each row's color at its nodes (a node holds one element of
    a color, so the bit goes with it)."""
    c = np.repeat(colors, rows.shape[1])
    used[rows.ravel(), c // 64] &= ~(_ONE << (c % 64).astype(np.uint64))


def _class_masks(conn: np.ndarray, color: np.ndarray,
                 num_nodes: int) -> np.ndarray:
    """The bitmasks of a valid coloring, one class at a time (the
    elements of a class share no node)."""
    num_colors = int(color.max()) + 1
    used = np.zeros((num_nodes, -(-num_colors // 64)), np.uint64)
    order = np.argsort(color, kind="stable")
    ptr = np.searchsorted(color[order], np.arange(num_colors + 1))
    for c in range(num_colors):
        els = order[ptr[c]:ptr[c + 1]]
        _set(used, conn[els], np.full(els.size, c))
    return used


# ---------------------------------------------------------------------------
# The rounds and the balance pass
# ---------------------------------------------------------------------------

def _color_rounds(conn: np.ndarray, ptr: np.ndarray,
                  inc: np.ndarray) -> Tuple[np.ndarray, int]:
    """Jones–Plassmann rounds (module docstring) over the incidence
    ``(ptr, inc)``; returns the colors and the number of rounds."""
    ne = conn.shape[0]
    num_nodes = ptr.size - 1
    color = np.full(ne, -1, np.int64)
    used = np.zeros((num_nodes, 1), np.uint64)
    rounds = 0

    def take(els):
        nonlocal used
        rows = conn[els]
        col, used = _first_free(used, rows)
        color[els] = col
        _set(used, rows, col)
        return rows

    # element order: the highest-priority uncolored element at a node is
    # the first uncolored one of its ascending incidence list, so a
    # per-node head pointer tracks it and only elements that just became
    # some node's head are looked at again
    head = ptr[:-1].copy()
    inc_ext = np.append(inc, ne)               # ne: the node is done

    def heads(nodes):
        return inc_ext[np.where(head[nodes] < ptr[nodes + 1],
                                head[nodes], inc.size)]

    first = heads(np.arange(num_nodes))
    cand = np.unique(first[first < ne])
    while cand.size and rounds < ORDERED_MAX_ROUNDS:
        rounds += 1
        ready = (first[conn[cand]] == cand[:, None]).all(axis=1)
        nodes = take(cand[ready]).ravel()
        np.add.at(head, nodes, 1)
        first[nodes] = nxt = heads(nodes)
        cand = np.unique(nxt[nxt < ne])

    # seeded random priorities for any elements left
    left = np.flatnonzero(color < 0)
    if left.size:
        pri = np.random.default_rng(COLORING_SEED).permutation(ne)
        keep = color[inc] < 0
        inc_node = np.repeat(np.arange(num_nodes), np.diff(ptr))[keep]
        inc_el = inc[keep]
        node_max = np.full(num_nodes, -1, np.int64)
        while left.size:
            rounds += 1
            starts = np.flatnonzero(np.r_[True, inc_node[1:]
                                          != inc_node[:-1]])
            node_max[inc_node[starts]] = np.maximum.reduceat(
                pri[inc_el], starts)
            chosen = pri[left] == node_max[conn[left]].max(axis=1)
            take(left[chosen])
            left = left[~chosen]
            keep = color[inc_el] < 0
            inc_node, inc_el = inc_node[keep], inc_el[keep]
    return color, rounds


def _balance(conn: np.ndarray, color: np.ndarray, num_nodes: int,
             max_rounds: int = BALANCE_MAX_ROUNDS) -> np.ndarray:
    """Shrink the larger color classes: each round takes the classes
    smallest first as destinations, and into each moves elements of every
    class at least two larger, largest first, that touch no element of
    the destination — at most half the size gap, so a destination never
    outgrows its source.  Elements of one class share no node, so a
    batch from one source moves together.  Never adds a color, never
    enlarges the largest class, and stops at a round that moves
    nothing."""
    ne = conn.shape[0]
    num_colors = int(color.max()) + 1
    if num_colors <= 1:
        return color
    color = color.copy()
    used = _class_masks(conn, color, num_nodes)
    sizes = np.bincount(color, minlength=num_colors)
    target = -(-ne // num_colors)
    for _ in range(max_rounds):
        if sizes.max() <= target:
            break
        # colors at each element's nodes when the round starts: a bit
        # that moves set since is caught by the exact check below
        forb = _forbidden(used, conn)
        # a small-integer key sorts by radix
        key = color.astype(np.uint16) if num_colors <= 1 << 16 else color
        members = np.argsort(key, kind="stable")
        bounds = np.searchsorted(color[members], np.arange(num_colors + 1))
        moved = False
        for d in np.argsort(sizes, kind="stable"):
            word, bit = d // 64, _ONE << np.uint64(d % 64)
            for c in np.argsort(-sizes, kind="stable"):
                gap = int(sizes[c] - sizes[d])
                if gap < 2:
                    break
                # the class at the round's start, less what left it since
                els = members[bounds[c]:bounds[c + 1]]
                els = els[(color[els] == c)
                          & ((forb[els, word] & bit) == 0)][:gap // 2]
                if els.size == 0:
                    continue
                rows = conn[els]
                ok = (_forbidden(used, rows)[:, word] & bit) == 0
                els, rows = els[ok], rows[ok]
                if els.size == 0:
                    continue
                _clear(used, rows, np.full(els.size, c))
                _set(used, rows, np.full(els.size, d))
                color[els] = d
                sizes[c] -= els.size
                sizes[d] += els.size
                moved = True
        if not moved:
            break
    return color


def color_elements(conn: np.ndarray, balance: bool = True,
                   provider: str = "greedy") -> Coloring:
    """Balanced coloring of the elements: no two elements of one color
    share a node.  ``greedy`` (the default) colors from the node–element
    incidence in vectorised rounds (module docstring); ``race`` runs
    RACE's recursive level groups over the element graph, which on a tet
    mesh (~24 elements at a node, so any classic coloring needs ≥ 24
    colors) cut the palette to a handful of sweeps.

    Recorded under the span ``assembly.element_coloring`` (labels
    ``provider``, ``num_colors`` and ``rounds``), with child spans
    ``.incidence``, ``.rounds`` and ``.balance`` for ``greedy``, whose
    rounds also count into ``element_coloring_rounds_total{provider}``."""
    if provider not in PROVIDERS:
        raise ValueError(f"unknown coloring provider {provider!r}; "
                         f"expected one of {PROVIDERS}")
    conn = np.asarray(conn)
    with obs.span("assembly.element_coloring", provider=provider) as sp:
        if provider == "race":
            col = color_graph(element_adjacency(conn),
                              include_indirect=False, balance=balance,
                              provider="race")
            sp.set_labels(num_colors=col.num_colors)
            return col
        ne = conn.shape[0]
        if ne == 0:
            return _finalize(np.zeros(0, np.int64))
        num_nodes = int(conn.max()) + 1
        with obs.span("assembly.element_coloring.incidence", ne=ne):
            ptr, inc = node_incidence(conn, num_nodes)
        with obs.span("assembly.element_coloring.rounds") as rs:
            color, rounds = _color_rounds(conn, ptr, inc)
            rs.set_labels(rounds=rounds)
        if balance:
            with obs.span("assembly.element_coloring.balance"):
                color = _balance(conn, color, num_nodes)
        obs.count("element_coloring_rounds_total", rounds,
                  provider=provider)
        col = _finalize(color)
        sp.set_labels(num_colors=col.num_colors, rounds=rounds)
        return col


def verify_element_coloring(conn: np.ndarray, col: Coloring) -> bool:
    """Chunk-aware invariant: no two elements of one color in *different*
    serial chunks share a node (hence no two share any scatter target,
    diagonal or off-diagonal).  Greedy colorings have singleton chunks —
    the classic per-element disjointness; RACE colorings may share nodes
    inside one level-group chunk, which the order-free ``.at[].add``
    scatter accumulates exactly.

    Numpy over the incidence: sorted by (color, node), the elements of
    each pair must all lie in one chunk."""
    conn = np.asarray(conn)
    ne, nen = conn.shape
    if ne == 0:
        return True
    chunk = (np.arange(ne, dtype=np.int64) if col.group_of_row is None
             else np.asarray(col.group_of_row, np.int64))
    key = (np.repeat(np.asarray(col.color_of_row, np.int64), nen)
           * (int(conn.max()) + 1) + conn.ravel())
    order = np.argsort(key)
    key, g = key[order], np.repeat(chunk, nen)[order]
    return not bool(((key[1:] == key[:-1]) & (g[1:] != g[:-1])).any())
