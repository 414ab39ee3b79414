"""Operator sources: how a session's graph reaches the tick program.

A :class:`~repro.stream.service.StreamingService` holds ONE source,
chosen when it starts from its resolved backend, mesh and panel-sharding
axes, in this order:

==========================  =====================  ========================
``ServiceConfig``           source                 the tick reads
==========================  =====================  ========================
``model_axes`` (+ mesh)     panel-sharded          ``ModelShardedBlocking``
mesh, pallas                edge-sharded blocked   ``ShardedNodeBlocking``
mesh, segment               edge-sharded segment   the edge buffer
pallas                      node-blocked           ``NodeBlocking``
segment                     segment                the edge buffer
==========================  =====================  ========================

The source owns every decision that follows from that choice: the
edge-capacity balance, the host-side layout, the group-key statics, the
per-session operands a group tick stacks, the keyword arguments of
:func:`repro.core.program.build_tick_program`, and the admission probe.
A layout change (a new layout type, or a new field the tick reads)
therefore edits this module and the tick builder, not the service.

Edge sharding (a mesh without ``model_axes``): shard ``s`` computes
``deg_s * v - A_s v`` from ITS contiguous slice of the capacity-padded
edge buffer (``kernels.edge_spmm.ops.ShardedNodeBlocking`` on pallas),
so one psum reconstructs ``L v`` and the dilation AXPY applies after
it.  Admission and growth round edge capacities up to a multiple of the
shard count (:meth:`OperatorSource.balance`), so every shard owns
``capacity / S`` slots; a shard whose slice is all padding contributes
exact zeros.

Panel sharding (``model_axes``): shard ``s`` owns panel rows
``[s * R, (s + 1) * R)`` and every half-edge destined there, so its
local rows are final (the AXPY fuses into the kernel epilogue) and a
mu-EG step ships its row assembly and 2k x 2k gram in one fused psum
(``core.program.build_tick_model_sharded``).  The layout re-buckets
edges by destination itself, on both backends: the segment path
scatters over the same per-shard arrays the kernel reads.
"""
from __future__ import annotations

import functools

import jax.numpy as jnp
import numpy as np

from repro.core import backend as backend_mod
from repro.core import program
from repro.core.distributed import num_edge_shards
from repro.kernels.edge_spmm import ops as es_ops
from repro.spectral import probes as spectral_probes


class OperatorSource:
    """One service's operator source (see the module docstring)."""

    def __init__(self, cfg):
        self.cfg = cfg
        self.backend = backend_mod.resolve_backend(cfg.backend)
        self.mesh = cfg.mesh
        # ServiceConfig refuses model_axes without a mesh
        self.model_axes = (tuple(cfg.model_axes)
                           if cfg.model_axes is not None else None)
        # the tick reads a blocked layout on pallas, and on panel
        # sharding with either backend
        self.blocked = (self.backend == "pallas"
                        or self.model_axes is not None)
        # the edge-balance contract stays on edge_axes with panel
        # sharding too: it is orthogonal to the row ownership
        self.edge_shards = (num_edge_shards(cfg.mesh, cfg.edge_axes)
                            if cfg.mesh is not None else 1)
        if self.model_axes is not None:
            self._build = functools.partial(
                es_ops.build_model_sharded_blocking,
                num_shards=program.num_model_shards(cfg.mesh,
                                                    self.model_axes))
        elif cfg.mesh is not None:
            self._build = functools.partial(
                es_ops.build_sharded_node_blocking,
                num_shards=self.edge_shards)
        else:
            self._build = es_ops.build_node_blocking

    def balance(self, capacity: int) -> int:
        """Smallest edge capacity >= ``capacity`` that divides evenly
        into the edge shards (the identity off a mesh).  Capacity
        classes and meshes are usually powers of two, so this matters
        for odd-shaped meshes only."""
        return capacity + (-capacity) % self.edge_shards

    def build(self, store):
        """Host-side blocked layout of the store's LIVE edges at this
        source's mesh shape, with ``block_n`` capped at the node
        capacity.  Free slots are dropped during bucketing, and the
        chunk count is snapped (``es_ops.snap_chunk_count``), so
        sessions of one capacity class with similar skew share one
        compiled program.  Edge mutations stale it.  A segment source's
        tick never reads it, but it is still built on request
        (``StreamingService._ensure_blocking``): the chip benchmark's CPU
        tests time a layout build that way."""
        return self._build(
            np.asarray(store.src), np.asarray(store.dst),
            np.asarray(store.weight), store.num_nodes,
            block_n=min(self.cfg.tick_block_n, store.num_nodes))

    def statics(self, layout) -> tuple:
        """The group key's part after (class, degree): the shapes the
        kernel compiles against."""
        if not self.blocked:
            return ()
        return (layout.block_n, layout.num_chunks, layout.block_e)

    def operands(self, sess) -> tuple:
        """A session's arrays, in the order the tick program takes them
        before ``(vs, cs, lrs, chunks)``."""
        if not self.blocked:
            st = sess.store
            return st.src, st.dst, st.weight
        b = sess.layout
        return b.u_local, b.other, b.weight, b.chunk_block, b.deg

    def tick_args(self, statics: tuple) -> dict:
        """Keyword arguments of ``program.build_tick_program``."""
        return dict(layout=statics or None, mesh=self.mesh,
                    edge_axes=self.cfg.edge_axes,
                    model_axes=self.model_axes)

    def probe(self, store, key, n: int):
        """SLQ admission probe through the matvec the tick runs: the
        row-sharded one (on a throwaway layout: probes run on admission
        and drift re-solves only), the psum-assembled edge-sharded one,
        or the single-device one.  ``probe_steps`` is jit-static and not
        clamped to n (Lanczos handles m >= n by sticky breakdown), so a
        capacity class shares one probe compile."""
        cfg = self.cfg
        n = jnp.asarray(n, jnp.int32)
        kw = dict(num_probes=cfg.probe_vectors, num_steps=cfg.probe_steps,
                  backend=self.backend)
        if self.model_axes is not None:
            return spectral_probes.probe_model_sharded(
                self.mesh, self.build(store), key, n,
                model_axes=self.model_axes, **kw)
        if self.mesh is not None:
            return spectral_probes.probe_sharded_edge_arrays(
                self.mesh, store.src, store.dst, store.weight, key, n,
                num_nodes=store.num_nodes, edge_axes=cfg.edge_axes, **kw)
        return spectral_probes.probe_edge_arrays(
            store.src, store.dst, store.weight, key, n,
            num_nodes=store.num_nodes, **kw)
