"""Device-mesh scenario parallelism.

The reference shards scenario *objects* over MPI ranks and Allreduces the
per-node x̄/x̄² vectors (ref. mpisppy/spbase.py:172 _calculate_scenario_ranks,
phbase.py:196-201). Here the scenario axis of every batch tensor is sharded
over a 1-D `jax.sharding.Mesh` axis ("scen"); the PH step is an ordinary
jitted function, and GSPMD turns the membership matmuls of
SPBase.compute_xbar (B_tᵀ(p⊙x) followed by B_t @ ...) into the
all-reduce/all-gather collectives that ride the ICI — the direct analog of
the reference's per-tree-node comm.Allreduce, chosen by the compiler
instead of hand-written.

Node contiguity (ScenarioTree.validate) guarantees that multistage
sub-node reductions touch contiguous mesh slices, minimizing cross-slice
traffic — the same property the reference engineers into its scenario->rank
map (ref. sputils.py:635-659).

Scenario counts that don't divide the mesh are padded with zero-probability
copies of the last scenario (probability renormalization is a no-op since
the pads carry p=0; xbar membership matmuls are probability-weighted, so
pads contribute nothing).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import obs

SCEN_AXIS = "scen"


def make_mesh(n_devices=None, devices=None) -> Mesh:
    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (SCEN_AXIS,))


def scenario_sharding(mesh: Mesh, rank: int = 1) -> NamedSharding:
    """Sharding that splits the leading (scenario) axis, replicates the rest."""
    spec = P(SCEN_AXIS, *([None] * (rank - 1)))
    return NamedSharding(mesh, spec)


def shard_arrays(mesh: Mesh, arrays: dict[str, jax.Array]) -> dict[str, jax.Array]:
    """device_put each (S, ...) array with the scenario axis sharded."""
    out = {}
    for k, v in arrays.items():
        out[k] = jax.device_put(v, scenario_sharding(mesh, v.ndim))
    return out


def replicated_sharding(mesh: Mesh, rank: int) -> NamedSharding:
    """Fully-replicated placement on the mesh (per-leaf; batch shipping
    of shared operands in core/spbase uses the same spec inline)."""
    return NamedSharding(mesh, P(*([None] * rank)))


def local_chunk_layout(shard_rows: int, chunk: int) -> tuple[int, int]:
    """(n_chunks, lc) for a per-device shard of ``shard_rows`` scenarios
    under the ``subproblem_chunk`` per-device microbatch bound: lc is
    rounded so n_chunks · lc covers the shard with the pad below one
    chunk-row per device. The SINGLE source of this formula — both the
    construction-time mesh padding (core/spbase) and the runtime chunk
    staging (core/ph._local_chunk) derive from it, and chunk_layout's
    "lc divides shard" invariant holds because the map is idempotent
    (re-applying it to n_chunks·lc returns the same lc)."""
    n_chunks = -(-shard_rows // int(chunk))
    return n_chunks, -(-shard_rows // n_chunks)


def colocate(parts):
    """Normalize a list of arrays onto one placement (the first part's
    device) when callers hand in arrays committed to different devices
    — the shared precondition of jnp.stack/concatenate. Same-placement
    inputs (the common case: single-device chunk states, or sharded
    states that all carry the mesh placement) pass through untouched."""
    if len({tuple(sorted(map(str, p.devices()))) for p in parts}) <= 1:
        return parts
    dev = next(iter(parts[0].devices()))
    return [jax.device_put(p, dev) for p in parts]


class ShardedScenarioOps:
    """Explicit-collective scenario-axis operations over the "scen" mesh
    axis — the SURVEY §5.7/§5.8 mapping made literal instead of left to
    GSPMD's partitioner:

    - ``xbar``/``combine``: Compute_Xbar, Update_W and the scaled-L1
      convergence as LOCAL segment-sums over the tree-node index
      followed by one ``psum`` over the named axis per stage — the
      subgroup reduction over axis slices for multistage trees (a node's
      scenarios occupy contiguous index ranges, so its partial sums are
      nonzero only on the mesh slice that owns them; the psum of the
      (N_t, k_t) node table IS the per-node Allreduce of the reference,
      ref. phbase.py:196-201). O(S·k) work replaces the O(S·N·k)
      membership matmuls — at 10k+ scenarios the (S, N) membership
      matrix stops being materialized at all.
    - ``to_chunks``/``from_chunks``: the sharded chunked hot loop's data
      staging. Chunk ci of the scenario axis is rows [ci·lc, (ci+1)·lc)
      of EVERY device's local shard (a local reshape — no device_put, no
      cross-device traffic), so each microbatch solve is one SPMD
      program with every device solving ``lc`` scenarios. The global
      scenario ids of a chunk are strided (``chunk_global_index``); the
      reassembled full batch comes back in natural order because each
      device's chunks concatenate to exactly its contiguous shard.

    All entry points are shard_map programs cached per (structure,
    shape) signature; every call is one jitted dispatch.
    """

    def __init__(self, mesh: Mesh, tree, slot_bounds, S: int):
        self.mesh = mesh
        self.n_devices = int(mesh.devices.size)
        if S % self.n_devices:
            raise ValueError(f"S={S} not divisible by the "
                             f"{self.n_devices}-device mesh (pad first: "
                             "pad_batch_for_mesh)")
        self.S = S
        self.shard_size = S // self.n_devices
        self.slot_bounds = tuple(slot_bounds)
        self.n_nodes = tuple(int(n) for n in tree.nodes_per_stage)
        # per-stage (S,) GLOBAL node ids, sharded like every other
        # per-scenario tensor so shard_map bodies see their local slice
        sh = scenario_sharding(mesh, 1)
        self.node_idx = tuple(
            jax.device_put(jnp.asarray(tree.node_path[:, t],
                                       dtype=jnp.int32), sh)
            for t in range(tree.node_path.shape[1]))
        self._fns = {}

    # ---- builders (cached shard_map programs) ----
    def _shard_map(self, body, in_specs, out_specs):
        return jax.jit(jax.shard_map(body, mesh=self.mesh,
                                     in_specs=in_specs,
                                     out_specs=out_specs,
                                     check_vma=False))

    def _spec(self, ndim, sharded=True):
        if not sharded:
            return P()
        return P(SCEN_AXIS, *([None] * (ndim - 1)))

    def _combine_fn(self, w_ndim, has_wmask, full):
        """The collective PH reduce: per-stage segment-sum + psum.
        ``full=True`` returns (xbar, xsqbar, W_new, conv) — the
        _ph_combine contract; ``full=False`` just xbar (the
        Compute_Xbar / APH FirstReduce surface)."""
        key = ("combine", w_ndim, has_wmask, full)
        if key in self._fns:
            return self._fns[key]
        import jax.ops as jops
        bounds, n_nodes = self.slot_bounds, self.n_nodes

        def _stage_means(xn, w, nidx, want_sq):
            outs, outs_sq = [], []
            for ni, N, (lo, hi) in zip(nidx, n_nodes, bounds):
                xt = xn[:, lo:hi]
                wt = w[:, lo:hi] if w_ndim == 2 \
                    else jnp.broadcast_to(w[:, None], xt.shape)
                num = jops.segment_sum(wt * xt, ni, num_segments=N)
                den = jops.segment_sum(wt, ni, num_segments=N)
                parts = [num, den]
                if want_sq:
                    parts.append(jops.segment_sum(wt * xt * xt, ni,
                                                  num_segments=N))
                with jax.named_scope("ph.psum"):
                    parts = jax.lax.psum(tuple(parts), SCEN_AXIS)
                outs.append((parts[0] / parts[1])[ni])
                if want_sq:
                    outs_sq.append((parts[2] / parts[1])[ni])
            xbar = jnp.concatenate(outs, axis=1)
            return (xbar, jnp.concatenate(outs_sq, axis=1)) if want_sq \
                else (xbar, None)

        if full:
            def body(xn, prob, w, W, rho, wmask, *nidx):
                K = xn.shape[1]
                xbar, xsqbar = _stage_means(xn, w, nidx, True)
                W_new = W + rho * (xn - xbar)
                if has_wmask:
                    W_new = jnp.where(wmask, W_new, 0.0)
                with jax.named_scope("ph.psum"):
                    conv = jax.lax.psum(
                        jnp.dot(prob, jnp.sum(jnp.abs(xn - xbar), axis=1)),
                        SCEN_AXIS) / K
                return xbar, xsqbar, W_new, conv

            n_idx = len(self.node_idx)
            in_specs = (self._spec(2), self._spec(1), self._spec(w_ndim),
                        self._spec(2), self._spec(2),
                        self._spec(2) if has_wmask else P()) \
                + (self._spec(1),) * n_idx
            out_specs = (self._spec(2), self._spec(2), self._spec(2), P())
        else:
            def body(xn, w, *nidx):
                xbar, _ = _stage_means(xn, w, nidx, False)
                return xbar

            in_specs = (self._spec(2), self._spec(w_ndim)) \
                + (self._spec(1),) * len(self.node_idx)
            out_specs = self._spec(2)
        fn = self._shard_map(body, in_specs, out_specs)
        self._fns[key] = fn
        return fn

    def _book_collective(self, dtype, full):
        """xfer.collective_bytes accounting lives HERE so every consumer
        of the collective entry points is counted — a call site that
        forgot its own counter_add would silently undercount the
        analyze sharding section's collective-traffic totals."""
        if obs.enabled():
            obs.counter_add(
                "xfer.collective_bytes",
                self.combine_collective_bytes(jnp.dtype(dtype).itemsize,
                                              full=full))

    def xbar(self, weights, xn):
        """Collective Compute_Xbar (nonanticipative per-node mean,
        broadcast back to scenarios)."""
        self._book_collective(xn.dtype, full=False)
        fn = self._combine_fn(int(weights.ndim), False, full=False)
        return fn(xn, weights, *self.node_idx)

    def xbar_traced(self, w_ndim, dtype, calls):
        """``xbar`` for a caller that traces it inside a program of its
        own (core/aph._aph_step): ``(fn, node_idx)`` with
        ``fn(xn, weights, *node_idx)`` the same collective, booked here
        for the ``calls`` means one launch of that program runs."""
        for _ in range(calls):
            self._book_collective(dtype, full=False)
        return (self._combine_fn(int(w_ndim), False, full=False),
                tuple(self.node_idx))

    def combine(self, xn, prob, weights, W, rho, wmask):
        """Collective _ph_combine: (xbar, xsqbar, W_new, conv)."""
        self._book_collective(xn.dtype, full=True)
        fn = self._combine_fn(int(weights.ndim), wmask is not None,
                              full=True)
        if wmask is None:
            wmask = jnp.zeros((), xn.dtype)   # unused placeholder leaf
        return fn(xn, prob, weights, W, rho, wmask, *self.node_idx)

    def combine_collective_bytes(self, itemsize, full=True):
        """Estimated bytes one combine's psums reduce (operand sizes:
        the per-stage (N_t, k_t) num/den[/sq] node tables + the conv
        scalar) — the ``xfer.collective_bytes`` accounting basis. An
        ESTIMATE of logical all-reduce payload, not measured link
        traffic (ring/tree algorithms multiply by ~2(n-1)/n)."""
        total = 0
        for N, (lo, hi) in zip(self.n_nodes, self.slot_bounds):
            per_stage = 3 if full else 2          # num + den (+ sq)
            total += per_stage * N * (hi - lo) * itemsize
        if full:
            total += itemsize                     # conv scalar
        return total

    # ---- sharded chunk staging ----
    def chunk_layout(self, lc: int):
        """(n_chunks, chunk_rows_global) for local chunk size ``lc``;
        raises unless lc divides the shard (pad the batch so it does —
        core/spbase sizes the mesh padding from subproblem_chunk)."""
        if self.shard_size % lc:
            raise ValueError(
                f"local chunk {lc} does not divide the per-device shard "
                f"{self.shard_size} (S={self.S} on {self.n_devices} "
                "devices) — the batch padding should have rounded S up")
        return self.shard_size // lc, lc * self.n_devices

    def chunk_global_index(self, ci: int, lc: int) -> np.ndarray:
        """Global scenario ids of sharded chunk ``ci`` in chunk-row
        order (device-major: row d·lc + r is local row ci·lc + r of
        device d's shard) — the gate/hospital bookkeeping map."""
        L = self.shard_size
        return np.concatenate([d * L + ci * lc + np.arange(lc)
                               for d in range(self.n_devices)])

    def to_chunks(self, tree, lc: int):
        """Reshape every (S, ...) leaf to (n_chunks, lc·n_dev, ...) with
        the chunk-row axis sharded — a LOCAL reshape per device, no
        collectives, no device_put. ``tree[ci]`` (leading-axis index)
        is then chunk ci's sharded slice."""
        leaves, treedef = jax.tree.flatten(tree)
        key = ("to_chunks", lc, treedef, tuple(v.ndim for v in leaves))
        fn = self._fns.get(key)
        if fn is None:
            n_chunks, _ = self.chunk_layout(lc)

            def body(*ls):
                return tuple(
                    a.reshape((n_chunks, lc) + a.shape[1:]) for a in ls)

            in_specs = tuple(self._spec(v.ndim) for v in leaves)
            out_specs = tuple(P(None, SCEN_AXIS, *([None] * (v.ndim - 1)))
                              for v in leaves)
            fn = self._shard_map(body, in_specs, out_specs)
            self._fns[key] = fn
        with obs.span("mesh.to_chunks", cat="ph"):
            return jax.tree.unflatten(treedef, fn(*leaves))

    def _map_chunks_fn(self, key, fn, treedef, ndims, lc, n_rep):
        """``map_chunks``' program for one (fn, tree structure, lc)."""
        key = ("map_chunks", key, lc, treedef, ndims, n_rep)
        prog = self._fns.get(key)
        if prog is None:
            n_chunks, _ = self.chunk_layout(lc)
            n_leaves = len(ndims)

            def body(*args):
                chunked = [a.reshape((n_chunks, lc) + a.shape[1:])
                           for a in args[:n_leaves]]
                return tuple(
                    fn(jax.tree.unflatten(treedef,
                                          [a[ci] for a in chunked]),
                       *args[n_leaves:])
                    for ci in range(n_chunks))

            in_specs = tuple(self._spec(nd) for nd in ndims) \
                + (P(),) * n_rep
            # out_shardings: the spec the eager ``chunks[name][ci]``
            # index ops hand out, letter for letter (shard_map alone
            # pads it with Nones: an equivalent placement, but a new
            # entry in every consumer's jit cache)
            prog = jax.jit(
                jax.shard_map(body, mesh=self.mesh, in_specs=in_specs,
                              out_specs=P(SCEN_AXIS), check_vma=False),
                out_shardings=NamedSharding(self.mesh, P(SCEN_AXIS)))
            self._fns[key] = prog
        return prog

    def map_chunks(self, key, fn, tree, lc, *rep):
        """``to_chunks`` and what reads each chunk, as ONE program: the
        local reshape of every (S, ...) leaf, chunk ci's slice of each
        and ``fn(chunk ci's tree, *rep)`` for every ci (``rep``:
        replicated operands), where ``to_chunks(tree)[name][ci]`` costs
        an eager index launch per leaf per chunk before ``fn`` can run.
        Returns a TUPLE of the per-chunk results, every leaf row-sharded
        with the placement those index ops hand out (so a program that
        took one takes the other without a second lowering). ``key``
        names ``fn`` in the program cache."""
        leaves, treedef = jax.tree.flatten(tree)
        prog = self._map_chunks_fn(key, fn, treedef,
                                   tuple(v.ndim for v in leaves), lc,
                                   len(rep))
        with obs.span("mesh.to_chunks", cat="ph"):
            return prog(*leaves, *rep)

    def from_chunks(self, parts):
        """Concatenate per-chunk (lc·n_dev, ...) sharded arrays back to
        the natural-order (S, ...) batch — each device concatenates its
        own chunk rows, which ARE its contiguous shard."""
        key = ("from_chunks", len(parts), parts[0].ndim)
        fn = self._fns.get(key)
        if fn is None:
            def body(*ps):
                return jnp.concatenate(ps, axis=0)

            in_specs = tuple(self._spec(p.ndim) for p in parts)
            fn = self._shard_map(body, in_specs, self._spec(parts[0].ndim))
            self._fns[key] = fn
        with obs.span("mesh.from_chunks", cat="ph"):
            return fn(*parts)


def pad_batch_for_mesh(batch, n_shards: int):
    """Pad a ScenarioBatch to a multiple of n_shards scenarios with
    zero-probability copies of the last scenario. Returns (batch, S_orig)."""
    S = batch.S
    rem = (-S) % n_shards
    if rem == 0:
        return batch, S
    import dataclasses

    def pad(a):
        a = np.asarray(a)
        return np.concatenate([a, np.repeat(a[-1:], rem, axis=0)], axis=0)

    tree = batch.tree
    from ..ir.tree import ScenarioTree
    new_tree = ScenarioTree(
        scen_names=tree.scen_names + [f"_pad{i}" for i in range(rem)],
        node_paths=np.concatenate([tree.node_path,
                                   np.repeat(tree.node_path[-1:], rem, axis=0)]),
        nodes_per_stage=tree.nodes_per_stage,
        nonant_names_per_stage=tree.nonant_names_per_stage,
        probabilities=np.concatenate([tree.probabilities, np.zeros(rem)]),
    )
    return dataclasses.replace(
        batch, tree=new_tree,
        c=pad(batch.c), c0=pad(batch.c0), P_diag=pad(batch.P_diag),
        A=batch.A if batch.shared_A else pad(batch.A),
        l=pad(batch.l), u=pad(batch.u),
        lb=pad(batch.lb), ub=pad(batch.ub),
        c_stage=pad(batch.c_stage), c0_stage=pad(batch.c0_stage),
        prob=new_tree.probabilities.copy(),
    ), S
