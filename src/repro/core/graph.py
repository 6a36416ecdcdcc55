"""Graph containers for FlowGNN.

The paper's central workload assumption is *zero preprocessing*: graphs arrive
as raw COO edge lists and are processed on the fly. We mirror that exactly —
``GraphBatch`` holds padded COO arrays in arrival order (never sorted, never
partitioned) plus validity masks. Everything downstream (message passing,
kernels, pooling) must be correct for *any* edge order; tests enforce this with
hypothesis permutation properties.

Padding convention:
  * padded nodes/edges are masked out via ``node_mask`` / ``edge_mask``;
  * padded edges point at node 0 — safe because their messages are neutralized
    per aggregation kind (0 for sum/mean, -inf for max, +inf for min);
  * multiple small graphs are packed into one batch; ``graph_ids`` maps each
    node to its graph for segment pooling (the paper streams graphs at batch
    size 1; batching here is the same packing used for its Fig. 7 sweep).

The serving path sends a batch to its device as ONE int32 buffer
(``FlatLayout``): ``pack`` fills it on the host, ``unflatten`` rebuilds the
same ``GraphBatch`` inside the jitted program. ``padding_fields`` is the one
definition of the masks and pooling ids both forms derive from the counts.
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class GraphBatch:
    """A batch of graphs in padded COO form (raw stream order)."""

    node_feat: jax.Array     # (N_pad, F_in) float — raw node features
    edge_feat: jax.Array     # (E_pad, D_in) float — raw edge features (zeros if none)
    senders: jax.Array       # (E_pad,) int32 — source node index per edge
    receivers: jax.Array     # (E_pad,) int32 — destination node index per edge
    node_mask: jax.Array     # (N_pad,) bool
    edge_mask: jax.Array     # (E_pad,) bool
    graph_ids: jax.Array     # (N_pad,) int32 — graph id per node (for pooling)
    graph_mask: jax.Array    # (G_pad,) bool — which graph slots are real
    node_pos: jax.Array      # (N_pad, P) float — positional field (DGN eigvec proxy)

    @property
    def n_node_pad(self) -> int:
        return self.node_feat.shape[0]

    @property
    def n_edge_pad(self) -> int:
        return self.senders.shape[0]

    @property
    def n_graph_pad(self) -> int:
        return self.graph_mask.shape[0]

    def num_nodes(self) -> jax.Array:
        return jnp.sum(self.node_mask.astype(jnp.int32))

    def num_edges(self) -> jax.Array:
        return jnp.sum(self.edge_mask.astype(jnp.int32))

    def in_degrees(self) -> jax.Array:
        """Per-node in-degree, computed on the fly (no preprocessing)."""
        ones = self.edge_mask.astype(jnp.float32)
        return jax.ops.segment_sum(ones, self.receivers, num_segments=self.n_node_pad)


def build_graph_batch(
    node_feat: np.ndarray,
    senders: np.ndarray,
    receivers: np.ndarray,
    *,
    edge_feat: Optional[np.ndarray] = None,
    node_pad: int,
    edge_pad: int,
    graph_offsets: Optional[np.ndarray] = None,
    graph_pad: int = 1,
    node_pos: Optional[np.ndarray] = None,
    pos_dim: int = 1,
) -> GraphBatch:
    """Pad raw COO arrays (host-side, numpy) into a GraphBatch.

    ``graph_offsets``: node-index boundaries between packed graphs,
    e.g. [0, n0, n0+n1, ...]; defaults to a single graph.
    """
    n, f = node_feat.shape
    e = senders.shape[0]
    if graph_offsets is None:
        graph_offsets = np.array([0, n])
    n_graphs = len(graph_offsets) - 1
    _check_fits(n, e, n_graphs, node_pad, edge_pad, graph_pad)
    if edge_feat is None:
        edge_feat = np.zeros((e, 1), dtype=np.float32)
    d = edge_feat.shape[1]
    if node_pos is None:
        node_pos = np.zeros((n, pos_dim), dtype=np.float32)

    nf = np.zeros((node_pad, f), dtype=np.float32)
    nf[:n] = node_feat
    ef = np.zeros((edge_pad, d), dtype=np.float32)
    ef[:e] = edge_feat
    snd = np.zeros((edge_pad,), dtype=np.int32)
    snd[:e] = senders
    rcv = np.zeros((edge_pad,), dtype=np.int32)
    rcv[:e] = receivers
    npos = np.zeros((node_pad, node_pos.shape[1]), dtype=np.float32)
    npos[:n] = node_pos

    ends = np.full((graph_pad,), n, dtype=np.int32)
    ends[:n_graphs] = graph_offsets[1:]
    nmask, emask, gids, gmask = padding_fields(
        np, n, e, n_graphs, ends, node_pad, edge_pad, graph_pad)

    return GraphBatch(
        node_feat=jnp.asarray(nf),
        edge_feat=jnp.asarray(ef),
        senders=jnp.asarray(snd),
        receivers=jnp.asarray(rcv),
        node_mask=jnp.asarray(nmask),
        edge_mask=jnp.asarray(emask),
        graph_ids=jnp.asarray(gids),
        graph_mask=jnp.asarray(gmask),
        node_pos=jnp.asarray(npos),
    )


def _check_fits(n: int, e: int, n_graphs: int, node_pad: int, edge_pad: int,
                graph_pad: int) -> None:
    if n > node_pad or e > edge_pad:
        raise ValueError(f"graph ({n} nodes, {e} edges) exceeds padding "
                         f"({node_pad}, {edge_pad})")
    if n_graphs > graph_pad:
        raise ValueError(f"{n_graphs} graphs exceed graph_pad={graph_pad}")


def padding_fields(xp, n, e, n_graphs, ends, node_pad: int, edge_pad: int,
                   graph_pad: int):
    """``(node_mask, edge_mask, graph_ids, graph_mask)`` of a padded batch.

    ``xp`` is numpy (host build) or jax.numpy (inside the program); ``n``,
    ``e`` and ``n_graphs`` are the real node, edge and graph counts and
    ``ends`` (``graph_pad`` entries) the node index where each graph ends,
    ``n`` past the last real graph. Masks are ``iota < count``. A real
    node's graph is the number of graphs that end at or before it; padded
    nodes pool into the last (masked) graph slot if it exists, else 0 —
    they are masked out of pooling anyway via ``node_mask``.
    """
    node = xp.arange(node_pad, dtype=xp.int32)
    gids = xp.sum(ends[:, None] <= node[None, :], axis=0, dtype=xp.int32)
    gids = xp.where(node < n, gids, xp.minimum(n_graphs, graph_pad - 1))
    return (node < n, xp.arange(edge_pad) < e, gids.astype(xp.int32),
            xp.arange(graph_pad) < n_graphs)


@dataclass(frozen=True)
class FlatLayout:
    """Where each field of one padded batch lies in a single int32 buffer.

    A pure function of the bucket and the model's input widths, so one
    compiled program per bucket reads every batch packed for it. In
    order: the node, edge and graph counts; ``ends`` (``graph_pad``
    words, see ``padding_fields``); node features, node positions, edge
    features (float32, stored bit for bit); senders; receivers. Masks and
    ``graph_ids`` are not sent: ``unflatten`` derives them from the
    counts by the same rules ``build_graph_batch`` uses, so the rebuilt
    ``GraphBatch`` holds the same bits as the host-built one.
    """

    node_pad: int
    edge_pad: int
    graph_pad: int
    node_feat_dim: int
    edge_feat_dim: int
    pos_dim: int

    _FLOAT = ("node_feat", "node_pos", "edge_feat")

    @functools.cached_property
    def fields(self) -> Dict[str, Tuple[int, int, Tuple[int, ...]]]:
        """name -> (first word, end word, shape)."""
        n, e = self.node_pad, self.edge_pad
        shapes = (("counts", (3,)), ("ends", (self.graph_pad,)),
                  ("node_feat", (n, self.node_feat_dim)),
                  ("node_pos", (n, self.pos_dim)),
                  ("edge_feat", (e, self.edge_feat_dim)),
                  ("senders", (e,)), ("receivers", (e,)))
        out, at = {}, 0
        for name, shape in shapes:
            size = int(np.prod(shape))
            out[name] = (at, at + size, shape)
            at += size
        return out

    @property
    def size(self) -> int:
        """Length of the buffer in int32 words."""
        return self.fields["receivers"][1]

    def _view(self, buf, name: str):
        a, b, shape = self.fields[name]
        return buf[a:b].reshape(shape)

    def pack(self, graphs: Sequence) -> np.ndarray:
        """The padded batch of ``graphs`` in one new int32 buffer (host).

        ``graphs`` are ``PackItem``s (``node_feat / senders / receivers``,
        their ``num_nodes / num_edges`` and optional ``edge_feat /
        node_pos``), packed in order with edge indices shifted by each
        graph's node offset; a graph without an optional field gets
        zeros, as in ``concat_raw_graphs``. A field of another
        width than the layout's raises, with one exception: where
        ``edge_feat_dim`` is 1 the model takes no edge features, admission
        lets any width through, and an ``edge_feat`` of another width is
        left out. The buffer is new on every call: a device put of it is
        asynchronous, so it must not be written again once handed over.
        """
        nodes = np.array([g.num_nodes for g in graphs], np.int32)
        edges = np.array([g.num_edges for g in graphs], np.int64)
        n, e, n_graphs = int(nodes.sum()), int(edges.sum()), len(graphs)
        _check_fits(n, e, n_graphs, self.node_pad, self.edge_pad,
                    self.graph_pad)
        words = np.zeros(self.size, np.int32)
        floats = words.view(np.float32)
        words[:3] = n, e, n_graphs
        ends = self._view(words, "ends")
        np.cumsum(nodes, out=ends[:n_graphs])
        ends[n_graphs:] = n
        _put_rows(self._view(floats, "node_feat"),
                  [g.node_feat for g in graphs], nodes)
        _put_rows(self._view(floats, "node_pos"),
                  [getattr(g, "node_pos", None) for g in graphs], nodes)
        efs = [getattr(g, "edge_feat", None) for g in graphs]
        if self.edge_feat_dim == 1:
            efs = [ef if ef is not None and ef.shape[1] == 1 else None
                   for ef in efs]
        _put_rows(self._view(floats, "edge_feat"), efs, edges)
        shift = np.repeat(ends[:n_graphs] - nodes, edges)
        for name in ("senders", "receivers"):
            idx = self._view(words, name)[:e]
            np.concatenate([getattr(g, name) for g in graphs], out=idx,
                           casting="unsafe")
            idx += shift
        return words

    def unflatten(self, words) -> GraphBatch:
        """The ``GraphBatch`` ``pack`` padded, rebuilt from its buffer
        inside the jitted program: static slices and bitcasts."""
        def field(name):
            x = self._view(words, name)
            if name in self._FLOAT:
                x = jax.lax.bitcast_convert_type(x, jnp.float32)
            return x

        node_mask, edge_mask, graph_ids, graph_mask = padding_fields(
            jnp, words[0], words[1], words[2], field("ends"), self.node_pad,
            self.edge_pad, self.graph_pad)
        return GraphBatch(
            node_feat=field("node_feat"), edge_feat=field("edge_feat"),
            senders=field("senders"), receivers=field("receivers"),
            node_mask=node_mask, edge_mask=edge_mask, graph_ids=graph_ids,
            graph_mask=graph_mask, node_pos=field("node_pos"))


def _put_rows(dst: np.ndarray, arrays, rows: np.ndarray) -> None:
    """Each graph's rows of one field, in order, into the top of ``dst``;
    a graph without the field (None) gets zero rows. Shapes must match
    ``dst`` exactly: a field of another width raises, nothing broadcasts."""
    if all(a is None for a in arrays):
        return                                  # the buffer is zero already
    np.concatenate([np.zeros((r, dst.shape[1]), dst.dtype) if a is None
                    else a for a, r in zip(arrays, rows)],
                   out=dst[:int(rows.sum())], casting="unsafe")


def concat_raw_graphs(graphs) -> dict:
    """Concatenate raw COO graphs (host-side numpy) for packed batching.

    ``graphs`` is a sequence of objects with ``node_feat / senders /
    receivers`` and optional ``edge_feat / node_pos`` attributes (e.g.
    ``repro.data.graphs.RawGraph`` or ``packing.PackItem``). Edge indices are
    shifted by each graph's node offset; returns the keyword arguments for
    :func:`build_graph_batch` (minus the padding sizes)::

        {node_feat, senders, receivers, edge_feat, node_pos, graph_offsets}

    ``edge_feat`` / ``node_pos`` are None when absent from every input.
    When only some graphs carry them, the gaps are zero-filled at the width
    the other graphs use — the same semantics ``build_graph_batch`` applies
    to a lone graph without them — so one bare graph cannot poison an
    entire pack. Width mismatches across graphs still fail loudly.
    """
    if not graphs:
        raise ValueError("cannot concatenate an empty graph list")

    def gather(attr: str, rows_of) -> Optional[np.ndarray]:
        vals = [getattr(g, attr, None) for g in graphs]
        if not any(v is not None for v in vals):
            return None
        width = next(v.shape[1] for v in vals if v is not None)
        return np.concatenate([
            v if v is not None else np.zeros((rows_of(g), width), np.float32)
            for g, v in zip(graphs, vals)
        ])

    offs = np.zeros(len(graphs) + 1, dtype=np.int64)
    for i, g in enumerate(graphs):
        offs[i + 1] = offs[i] + g.node_feat.shape[0]
    return {
        "node_feat": np.concatenate([g.node_feat for g in graphs]),
        "senders": np.concatenate(
            [g.senders + offs[i] for i, g in enumerate(graphs)]),
        "receivers": np.concatenate(
            [g.receivers + offs[i] for i, g in enumerate(graphs)]),
        "edge_feat": gather("edge_feat", lambda g: g.senders.shape[0]),
        "node_pos": gather("node_pos", lambda g: g.node_feat.shape[0]),
        "graph_offsets": offs,
    }


def pad_bucket(n: int, buckets=(32, 64, 128, 256, 512, 1024, 4096, 16384)) -> int:
    """Smallest padding bucket holding ``n`` (streaming engine jits one program
    per bucket so arbitrary arriving graphs reuse compiled code)."""
    for b in buckets:
        if n <= b:
            return b
    # round up to next power of two beyond the table
    b = 1 << int(np.ceil(np.log2(max(n, 1))))
    return b


def permute_edges(g: GraphBatch, perm: np.ndarray) -> GraphBatch:
    """Reorder the edge list (used by tests: results must be invariant)."""
    perm = jnp.asarray(perm)
    return dataclasses.replace(
        g,
        edge_feat=g.edge_feat[perm],
        senders=g.senders[perm],
        receivers=g.receivers[perm],
        edge_mask=g.edge_mask[perm],
    )
