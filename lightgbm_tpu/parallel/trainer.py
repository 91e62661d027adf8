"""Distributed tree learning over a `jax.sharding.Mesh`.

TPU-native replacement for the reference's socket/MPI parallel learners
(src/treelearner/parallel_tree_learner.h, src/network/): the custom
Bruck/recursive-halving collectives become XLA collectives over ICI inside
``shard_map``:

  * ``tree_learner=data``    — rows sharded over the 'data' axis; local
    histograms are summed with ``psum`` (the reference uses ReduceScatter by
    feature then an arg-max Allreduce of SplitInfo,
    data_parallel_tree_learner.cpp:282-441).
  * ``tree_learner=feature`` — rows replicated; per-device feature masks shard
    the split search; the winner is agreed with an all-gather + arg-max
    (feature_parallel_tree_learner.cpp:71).
  * ``tree_learner=voting``  — PV-Tree (voting_parallel_tree_learner.cpp):
    rows sharded, leaf histograms stay device-local; each device votes its
    top-k features by local gain, the global top-2k are elected via a
    ``psum`` of votes, and only the elected features' histograms cross ICI
    before the (globally identical) split evaluation.

World size is fixed for the life of the trainer, matching the reference's
static `Network::Init` posture; recovery is checkpoint/restart.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import Config
from ..dataset import BinnedDataset
from ..models.learner import SerialTreeLearner
from ..obs import scopes
from ..utils import log

AXIS = "data"


class ShardedTreeBuilder:
    """Builds trees SPMD over an N-device mesh.

    Rows are padded to a multiple of the mesh size; each device holds its
    rows as the ``(pb_rows, N_pad_local)`` block the tree learner reads.
    """

    def __init__(self, dataset: BinnedDataset, config: Config,
                 mesh: Optional[Mesh] = None, mode: Optional[str] = None):
        self.config = config
        self.dataset = dataset
        if mesh is None:
            devices = np.asarray(jax.devices())
            mesh = Mesh(devices, (AXIS,))
        self.mesh = mesh
        self.ndev = mesh.devices.size
        mode = mode or {"data": "data", "feature": "feature",
                        "voting": "voting"}.get(config.tree_learner, "data")
        self.mode = mode
        # multi-process SPMD: `dataset` holds THIS RANK's rows only
        # (rank-sharded by the distributed data plane); each process
        # contributes its block of the global mesh array.  Mirrors the
        # reference's one-rank-per-machine socket/MPI learners
        # (parallel_tree_learner.h) with the collectives moved into XLA.
        self.nproc = jax.process_count()
        self.local_ndev = (len([d for d in self.mesh.devices.flat
                                if d.process_index == jax.process_index()])
                           if self.nproc > 1 else self.ndev)

        def _put(arr, sharding):
            # single-process: plain device_put; multi-process: this rank's
            # block of the global mesh array
            if self.nproc > 1:
                return jax.make_array_from_process_local_data(sharding, arr)
            return jax.device_put(arr, sharding)
        self._put = _put

        # The mesh-resident bins are ``(pb_rows, ndev * N_pad_local)``
        # sharded ``P(None, AXIS)``: each device holds its rows as the
        # ``(pb_rows, N_pad_local)`` block the tree learner reads (rows on
        # the lane axis, front and tail pad in place, bins sublane-padded
        # where the partition kernel runs), so neither the build nor a
        # layout init pads or transposes anything.  Where the host holds
        # the binned matrix, each block is cut from it and put on its own
        # device: nothing sized by the global row count visits a device.
        # A dataset that lives only in its device ingest (construct_device
        # =on, streamed sketch construction) is relaid on the device from
        # the ``(G, N_pad)`` master buffer instead.  Multi-process: each
        # rank's dataset holds only ITS rows and places its own devices'
        # blocks of the global array.
        di = getattr(dataset, "device_ingest", None)
        self._used_device_reshard = (di is not None and self.nproc == 1
                                     and dataset.binned is None)
        if self._used_device_reshard:
            N, G = di.N, di.G           # geometry without materializing
            binned = None
        else:
            binned = dataset.host_binned()
            if binned is None:
                raise ValueError(
                    "dataset has no binned data (construct it first)")
            N, G = binned.shape         # local rows when multi-process
        sharding = NamedSharding(self.mesh, P(AXIS))
        if self.nproc > 1:
            from . import network
            if self.mode == "feature":
                # the reference's feature-parallel keeps the FULL data on
                # every machine (docs/Parallel-Learning-Guide.rst); verify
                # the ranks agree on the row count
                if len(set(int(v) for v in network.global_array(
                        float(N)))) != 1:
                    raise ValueError(
                        "tree_learner=feature requires the full dataset "
                        "on every machine (rank row counts differ)")
                self.N = N
            else:
                self.N = int(network.global_sum([float(N)])[0])
            # one static per-device row count across the whole mesh
            self.local_n = int(network.global_sync_by_max(
                float(-(-N // self.local_ndev))))
        else:
            self.N = N
            self.local_n = (N + self.ndev - 1) // self.ndev
        if self.mode == "feature":
            self.local_n = self.N
        self.learner = SerialTreeLearner(
            dataset, config, axis_name=AXIS, parallel_mode=mode,
            num_shards=self.ndev, local_num_data=self.local_n,
            global_num_data=self.N)
        if self.mode == "feature":
            counts = [self.N] * self.local_ndev
        else:
            counts = [min(self.local_n, max(0, N - d * self.local_n))
                      for d in range(self.local_ndev)]
        if self._used_device_reshard:
            self.binned_sharded = self._device_reshard(di, N, G)
        else:
            self.binned_sharded = self._host_blocks(binned, counts)
        self.local_counts = _put(np.asarray(counts, dtype=np.int32), sharding)
        from ..obs import memory as obs_memory
        obs_memory.register(
            "parallel.binned_sharded", self,
            lambda sb: [sb.binned_sharded, sb.local_counts])

        lr = self.learner

        def build_shard(binned, grad, hess, bag_cnt, feature_mask, seed,
                        feat_used, lazy_aux):
            # binned: this shard's (pb_rows, N_pad) block; grad/hess:
            # (local_n,); bag_cnt: (1,) local in-bag rows (== local valid
            # rows without sampling)
            part_bins = binned
            grad_l = grad[: lr.N]
            hess_l = hess[: lr.N]
            if self.mode == "feature":
                # shard the split search: contiguous feature blocks per device
                d = jax.lax.axis_index(AXIS)
                F = lr.F
                per = (F + self.ndev - 1) // self.ndev
                fidx = jnp.arange(F)
                mine = (fidx >= d * per) & (fidx < (d + 1) * per)
                feature_mask = feature_mask & mine
            aux0 = lazy_aux[:, : lr.N] if lazy_aux is not None else None
            return lr._build_impl(part_bins, grad_l, hess_l,
                                  bag_cnt[0], feature_mask, seed, feat_used,
                                  aux0)

        row_spec = P() if self.mode == "feature" else P(AXIS)
        bins_spec = P() if self.mode == "feature" else P(None, AXIS)
        has_lazy = lr.cegb_lazy is not None
        aux_spec = (P(None, AXIS) if self.mode != "feature" else P()) \
            if has_lazy else None
        in_specs = (bins_spec, row_spec, row_spec, P(AXIS), P(), P(), P()) \
            + ((aux_spec,) if has_lazy else ())
        out_specs = (P(), aux_spec) if has_lazy else P()

        def wrapper(binned, grad, hess, bag_cnt, feature_mask, seed,
                    feat_used, *maybe_aux):
            rec = build_shard(binned, grad, hess, bag_cnt, feature_mask,
                              seed, feat_used,
                              maybe_aux[0] if maybe_aux else None)
            # model-lifetime cegb-lazy persistence: scatter this shard's
            # partitioned used-feature bitset back to ITS original rows
            # (shards own contiguous row blocks, so row-sharded output
            # reassembles the full original-order aux)
            aux_out = None
            if has_lazy:
                aux_out = lr.lazy_aux_to_original_order(rec)
            # drop per-shard-varying state (partition arrays and LOCAL leaf
            # offsets/counts) — only globally-identical values may be
            # replicated out; consumers must use leaf_cnt_g
            # ("hist" is also dropped: per-leaf histograms are device-local
            # in voting mode and no consumer reads them — replicating the
            # (L, G, B, 2) tensor would cost a full all-reduce per tree)
            rec = {k: v for k, v in rec.items()
                   if k not in ("indices", "part_bins", "part_grad",
                                "part_hess", "part_ghi", "sc32",
                                "sc_bins", "sc_ghi",
                                "part_aux", "sc_aux",
                                "leaf_start", "leaf_cnt", "hist")}

            def replicate(x):
                # values are identical on every device; pmax proves
                # replication to shard_map's type system
                if x.dtype == jnp.bool_:
                    return jax.lax.pmax(x.astype(jnp.int32), AXIS).astype(jnp.bool_)
                return jax.lax.pmax(x, AXIS)

            with scopes.scope("hist_sync"):
                rec = jax.tree.map(replicate, rec)
            if has_lazy:
                if self.mode == "feature":
                    # rows replicated: the aux is identical on every device
                    aux_out = jax.lax.pmax(aux_out, AXIS)
                return rec, aux_out
            return rec

        # the interpreter runs a kernel's body as plain operations whose
        # constants shard_map's varying-type check takes for unvarying:
        # the check is off for interpreted kernels only (the compiled
        # kernels declare their outputs varying, ops.varying_like)
        self._build_sharded = jax.jit(jax.shard_map(
            wrapper, mesh=self.mesh,
            in_specs=in_specs, out_specs=out_specs,
            check_vma=not self.interpreted_kernels))

    # ------------------------------------------------------------------
    @property
    def interpreted_kernels(self) -> bool:
        lr = self.learner
        return lr._interp and lr.plan.partition == "pallas"

    def _bins_sharding(self) -> NamedSharding:
        return NamedSharding(
            self.mesh, P() if self.mode == "feature" else P(None, AXIS))

    def _host_blocks(self, binned: np.ndarray, counts) -> jax.Array:
        """One ``(pb_rows, N_pad_local)`` block per local device, cut from
        the host-binned rows and put on that device, assembled into the
        global mesh array: the bins reach a device only as its own
        block."""
        lr = self.learner
        C, n_pad, G = lr.row0, lr.N_pad, binned.shape[1]
        sharding = self._bins_sharding()
        shape = (lr._pb_rows,
                 n_pad if self.mode == "feature" else self.ndev * n_pad)
        mine = [d for d in self.mesh.devices.flat
                if d.process_index == jax.process_index()]
        placed = []
        for i, dev in enumerate(mine):
            block = np.zeros((lr._pb_rows, n_pad), binned.dtype)
            rows = (binned[:counts[i]] if self.mode == "feature" else
                    binned[i * self.local_n:i * self.local_n + counts[i]])
            block[:G, C:C + len(rows)] = rows.T
            placed.append(jax.device_put(block, dev))
            del block
        return jax.make_array_from_single_device_arrays(
            shape, sharding, placed)

    def _device_reshard(self, di, N: int, G: int) -> jax.Array:
        """On-device relayout of the ingest master buffer to the mesh
        layout: ``(G, N_pad)`` -> per-device ``(pb_rows, N_pad_local)``
        blocks, bit-identical to ``_host_blocks``.  One jitted program;
        ``out_shardings`` places the blocks, so the matrix never visits
        the host and no (N, G) host copy materializes."""
        lr = self.learner
        C0 = di.row0
        C, n_pad, pb = lr.row0, lr.N_pad, lr._pb_rows
        buf = di.live_buffer()
        ndev, local_n = self.ndev, self.local_n
        if self.mode == "feature":
            def relay(b):
                return jnp.pad(b[:G, C0:C0 + N],
                               ((0, pb - G), (C, n_pad - C - N)))
        else:
            def relay(b):
                rows = jnp.pad(b[:G, C0:C0 + N],
                               ((0, pb - G), (0, ndev * local_n - N)))
                rows = jnp.pad(rows.reshape(pb, ndev, local_n),
                               ((0, 0), (0, 0), (C, n_pad - C - local_n)))
                return rows.reshape(pb, ndev * n_pad)
        # once-per-startup relayout: the trace is the product (shapes
        # differ per dataset, nothing to rebind)
        return jax.jit(relay,                    # jaxlint: ok=JL002
                       out_shardings=self._bins_sharding())(buf)

    def pad_rows(self, arr: np.ndarray) -> jnp.ndarray:
        """Pad a per-row array (process-local rows when multi-process) to
        the mesh row layout and shard it."""
        arr = np.asarray(arr, dtype=np.float32)
        if self.mode == "feature":
            return self._put(arr, NamedSharding(self.mesh, P()))
        total = self.local_ndev * self.local_n
        if len(arr) < total:
            arr = np.concatenate([arr, np.zeros(total - len(arr), np.float32)])
        return self._put(arr, NamedSharding(self.mesh, P(AXIS)))

    def pad_aux(self, aux) -> jnp.ndarray:
        """Shard the (aux_rows, N) cegb-lazy bitset over the mesh rows
        (replicated under feature-parallel).  The previous iteration's
        sharded output passes through untouched — build_tree returns the
        aux in mesh layout so it never materializes on the host (the
        shards may not even be host-addressable under multi-process)."""
        lr = self.learner
        # the pass-through check sees the GLOBAL array shape (all mesh
        # devices), while host-side padding below builds the LOCAL block
        total_global = (self.N if self.mode == "feature"
                        else self.ndev * self.local_n)
        if isinstance(aux, jax.Array) and aux.ndim == 2 \
                and aux.shape[1] == total_global and aux.dtype == jnp.int32:
            return aux
        if aux is None:
            aux = np.zeros((lr.aux_rows, self.N), np.int32)
        aux = np.asarray(aux, dtype=np.int32)
        if self.mode == "feature":
            return self._put(aux, NamedSharding(self.mesh, P()))
        total_local = self.local_ndev * self.local_n
        if aux.shape[1] < total_local:
            aux = np.concatenate(
                [aux, np.zeros((aux.shape[0], total_local - aux.shape[1]),
                               np.int32)], axis=1)
        return self._put(aux, NamedSharding(self.mesh, P(None, AXIS)))

    def build_tree(self, grad, hess, feature_mask=None,
                   seed: int = 0, feat_used=None,
                   bag_mask=None, lazy_aux=None):
        lr = self.learner
        if feature_mask is None:
            feature_mask = jnp.ones((lr.F,), dtype=bool)
        if feat_used is None:
            feat_used = jnp.zeros((lr.F,), dtype=bool)
        if bag_mask is None:
            bag_counts = self.local_counts
        else:
            # bagging/GOSS masks are full-length row predicates; each shard
            # needs ITS in-bag count for count estimation (the reference's
            # bagging composes with every parallel learner, bagging.hpp:13)
            m = np.asarray(bag_mask).astype(bool)
            if self.mode == "feature":
                counts = [int(m.sum())] * self.local_ndev
            else:
                counts = [int(m[d * self.local_n:(d + 1) * self.local_n]
                              .sum()) for d in range(self.local_ndev)]
            bag_counts = self._put(np.asarray(counts, np.int32),
                                   NamedSharding(self.mesh, P(AXIS)))
        args = (self.binned_sharded, self.pad_rows(grad),
                self.pad_rows(hess), bag_counts,
                feature_mask, jnp.int32(seed), feat_used)
        if self.learner.cegb_lazy is not None:
            return self._build_sharded(*args, self.pad_aux(lazy_aux))
        return self._build_sharded(*args)

    def _build_lowered_hlo(self, grad, hess) -> str:
        """Optimized HLO of the sharded tree build (test/inspection hook:
        verifies which collectives the histogram sync lowers to)."""
        lr = self.learner
        args = (self.binned_sharded, self.pad_rows(grad),
                self.pad_rows(hess), self.local_counts,
                jnp.ones((lr.F,), dtype=bool), jnp.int32(0),
                jnp.zeros((lr.F,), dtype=bool))
        return self._build_sharded.lower(*args).compile().as_text()
