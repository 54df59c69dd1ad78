"""SPBase: the root runtime object of every algorithm engine / cylinder.

The reference's SPBase (ref. mpisppy/spbase.py:42-114) partitions scenario
names over MPI ranks, instantiates local Pyomo models, attaches nonant
bookkeeping, and builds per-tree-node communicators. The TPU redesign holds
the *entire* scenario batch as device arrays (the scenario axis is a mesh
axis when sharded; see parallel/), so "partitioning" is a sharding
annotation rather than object distribution:

- probabilities / nonant indices  -> arrays from the ScenarioBatch
  (ref. spbase.py:272 _attach_nonant_indices, :353 node probabilities)
- per-tree-node communicators     -> per-stage membership matmuls
  (ref. spbase.py:311 _create_communicators)
- gather_var_values_to_rank0      -> host transfer of the solution block
  (ref. spbase.py:516)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..ir.batch import ScenarioBatch
from ..ops.qp_solver import QPData

# Above this size, host->device shipping goes structure-aware: a
# reference-scale UC batch shipped dense is a 2.7 GB constraint matrix
# plus ~0.7 GB of scenario vectors at S=1024, every byte of it staged
# through host memory first. The constraint matrix is ~0.03% dense and
# the scenario vectors are one template plus a handful of patched
# columns per scenario — megabytes of real information — so the
# device-side arrays are BUILT by scatter instead. (The attached v5e's
# host->device link moved 1 GiB in 0.145 s = 7.4 GB/s — CHANGES.md
# PR 24 — so the dense ship would cost well under a second there; what
# the scatter build still saves is the dense host staging copy.)
_SHIP_DENSE_LIMIT = 32 * 1024 * 1024


def ship_stacked(a_np, t):
    """(S, ...) stacked host array -> device array of dtype ``t``,
    shipping only the scenario-0 template plus the columns where any
    scenario differs when that is substantially smaller than the dense
    array (true for structure-shared models, where randomness touches
    a few rhs/bound entries per scenario)."""
    a = np.asarray(a_np)
    itemsize = np.dtype(t).itemsize
    if a.ndim < 2 or a.nbytes < _SHIP_DENSE_LIMIT:
        if obs.enabled():
            obs.counter_add("xfer.h2d_bytes", a.size * itemsize)
        return jnp.asarray(a, t)
    S = a.shape[0]
    flat = a.reshape(S, -1)
    tmpl = flat[0]
    diff = np.flatnonzero((flat != tmpl[None, :]).any(axis=0))
    patch_bytes = (tmpl.size + S * diff.size) * itemsize
    if patch_bytes > a.nbytes // 8:
        if obs.enabled():
            obs.counter_add("xfer.h2d_bytes", a.size * itemsize)
        return jnp.asarray(a, t)
    if obs.enabled():
        # the structure-aware ship moves template + patched columns
        # only; the counter records what actually crossed
        obs.counter_add("xfer.h2d_bytes", patch_bytes)
    base = jnp.broadcast_to(jnp.asarray(tmpl, t), flat.shape)
    if diff.size:
        base = base.at[:, jnp.asarray(diff)].set(
            jnp.asarray(flat[:, diff], t))
    return base.reshape(a.shape)


def ship_shared_matrix(A2d, t, split=False):
    """Shared (m, n) constraint matrix -> device dense array (or the
    df32 SplitMatrix pair), built by index scatter from the host's
    sparse representation when dense shipping would dominate."""
    from ..ops.qp_solver import SplitMatrix, split_f32_np

    A = np.asarray(A2d)
    n_parts = 2 if split else 1
    part_dt = jnp.float32 if split else t
    dense_bytes = A.size * np.dtype(part_dt).itemsize * n_parts
    rows, cols = np.nonzero(A)
    sparse_bytes = rows.size * (8 + 4 * n_parts)
    use_scatter = dense_bytes >= _SHIP_DENSE_LIMIT \
        and sparse_bytes < dense_bytes // 8
    if obs.enabled():
        obs.counter_add("xfer.h2d_bytes",
                        sparse_bytes if use_scatter else dense_bytes)

    if split:
        from ..ops.packed import analyze_structure

        # host structure discovery (ops/packed.py) while the pattern is
        # in hand: the skeleton ships as kilobytes of indices and lets
        # qp_setup build the packed matvec form that carries the hot
        # loop (the round-4 kernel's 3.8% MFU was dense passes streaming
        # zeros)
        struct = analyze_structure(rows, cols, A.shape[0], A.shape[1])
        hi_np, lo_np = split_f32_np(A)
        if not use_scatter:
            return SplitMatrix(jnp.asarray(hi_np), jnp.asarray(lo_np),
                               struct=struct)
        r = jnp.asarray(rows.astype(np.int32))
        c = jnp.asarray(cols.astype(np.int32))
        z = jnp.zeros(A.shape, jnp.float32)
        return SplitMatrix(z.at[r, c].set(jnp.asarray(hi_np[rows, cols])),
                           z.at[r, c].set(jnp.asarray(lo_np[rows, cols])),
                           struct=struct)
    if not use_scatter:
        return jnp.asarray(A, t)
    r = jnp.asarray(rows.astype(np.int32))
    c = jnp.asarray(cols.astype(np.int32))
    return jnp.zeros(A.shape, t).at[r, c].set(
        jnp.asarray(A[rows, cols], t))


def compute_xbar(memberships, slot_slices, weights, xn):
    """Nonanticipative mean per tree node, broadcast back to scenarios.

    xn: (S, K) nonant slots. Per non-leaf stage t with membership B_t:
    xbar = B_t (B_tᵀ(w⊙x) / B_tᵀw) — dense matmuls that become
    local-matmul + psum when the scenario axis is sharded. This replaces
    the per-node MPI Allreduce in Compute_Xbar (ref. phbase.py:144-221).

    ``weights`` is the scenario probability vector (S,) — or, with
    VARIABLE probabilities (ref. spbase.py:369-419 variable_probability:
    per-variable prob_coeff attached by the scenario creator), an (S, K)
    block of per-(scenario, slot) weights; the per-node average is then
    slot-wise weighted. Free function so jitted steps can take
    memberships/weights as ARGUMENTS (not baked-in constants);
    SPBase.compute_xbar wraps it."""
    outs = []
    for B, sl in zip(memberships, slot_slices):
        # slot ranges may arrive as (start, stop) int pairs — the
        # spelling jitted steps pass as STATIC arguments
        # (core/ph._ph_reduce, SPBase.slot_bounds)
        if isinstance(sl, tuple):
            sl = slice(*sl)
        xt = xn[:, sl]
        if weights.ndim == 2:
            w = weights[:, sl]
            den = B.T @ w                       # (N, k) per-slot masses
            num = B.T @ (w * xt)
            outs.append(B @ (num / den))
        else:
            pnode = B.T @ weights
            num = B.T @ (weights[:, None] * xt)
            outs.append(B @ (num / pnode[:, None]))
    return jnp.concatenate(outs, axis=1)


class SPBase:
    def __init__(self, batch: ScenarioBatch, options=None, dtype=None,
                 variable_probability=False, mesh=None):
        """`mesh`: optional jax Mesh whose first axis shards the scenario
        dimension of every batch tensor (see parallel/mesh.py). When given,
        the batch is zero-probability-padded to the mesh size and all
        jitted engine steps compile to SPMD programs with XLA-chosen
        collectives for the nonant reductions."""
        self._S_orig = batch.S
        if mesh is not None:
            from ..parallel.mesh import local_chunk_layout, \
                pad_batch_for_mesh
            n_dev = int(mesh.devices.size)
            mult = n_dev
            chunk = int((options or {}).get("subproblem_chunk", 0) or 0)
            if n_dev > 1 and chunk:
                # sharded chunked mode (core/ph._solve_loop_chunked):
                # ``subproblem_chunk`` bounds the PER-DEVICE microbatch,
                # and each chunk is a local slice of every device's
                # shard — so the shard must divide evenly into local
                # chunks. Round S up so it does (shared formula with
                # the runtime chunk staging — mesh.local_chunk_layout
                # keeps the pad below one chunk-row per device).
                L0 = -(-batch.S // n_dev)
                if chunk < L0:
                    n_chunks, lc = local_chunk_layout(L0, chunk)
                    mult = n_dev * n_chunks * lc
            batch, self._S_orig = pad_batch_for_mesh(batch, mult)
        self.mesh = mesh
        self.batch = batch
        self.options = dict(options or {})
        self.dtype = dtype or (jnp.float64 if jax.config.jax_enable_x64 else jnp.float32)
        self.spcomm = None  # set by the cylinder layer (ref. spbase.py:503)

        t = self.dtype
        b = batch
        self.prob = jnp.asarray(b.prob, t)
        # variable_probability: False (default) | True (skip the sum
        # check, reference flag semantics) | an (S, K) array of
        # per-(scenario, nonant-slot) weights used for the xbar averages
        # (ref. spbase.py:369-419: per-variable prob_coeff)
        self.vprob = None
        if variable_probability is not False and \
                not isinstance(variable_probability, bool):
            vp = np.asarray(variable_probability, dtype=np.float64)
            S_orig = getattr(self, "_S_orig", b.S)
            if vp.shape == (S_orig, b.K) and S_orig != b.S:
                # mesh padding added zero-probability scenarios; their
                # per-variable weights are zero too
                vp = np.concatenate(
                    [vp, np.zeros((b.S - S_orig, b.K))], axis=0)
            if vp.shape != (b.S, b.K):
                raise ValueError(f"variable_probability must be (S, K) = "
                                 f"({S_orig}, {b.K}), got {vp.shape}")
            # every tree NODE needs positive mass on every slot it owns —
            # a zero per-node denominator would silently NaN the averages
            for s_, sl in enumerate(b.stage_slot_slices):
                B = b.tree.membership(s_ + 1)
                if (B.T @ vp[:, sl] <= 0).any():
                    raise ValueError(
                        f"stage {s_ + 1}: some tree node has zero total "
                        "variable-probability mass on a nonant slot")
            self.vprob = jnp.asarray(vp, t)
        elif not variable_probability \
                and not self.options.get("partial_probabilities") \
                and abs(float(b.prob.sum()) - 1.0) > 1e-6:
            # partial_probabilities: this engine holds one SHARD of the
            # scenario set (core/aph_shard.py) — its locals carry their
            # GLOBAL probabilities, summing to the shard's mass, exactly
            # like a reference rank's local scenarios (ref. spbase.py:
            # 242 _create_scenarios; the sum check there is an Allreduce)
            raise ValueError("scenario probabilities must sum to 1 "
                             "(ref. spbase.py:443 checks)")
        # scenario-source selection (mpisppy_tpu/stream,
        # doc/streaming.md): a non-resident source replaces the
        # full-width device residency of the five per-scenario vector
        # fields (l/u/lb/ub/c) with per-chunk staging — built below
        # once shared structure is established; everything the source
        # does NOT cover ships exactly as before
        self._stream_source = None
        stream_kind = str(self.options.get("scenario_source",
                                           "resident"))
        from ..utils.config import STREAM_SOURCES
        if stream_kind not in STREAM_SOURCES:
            raise ValueError(f"unknown scenario_source {stream_kind!r};"
                             f" known: {STREAM_SOURCES}")
        streaming = stream_kind != "resident"
        if streaming and not int(self.options.get("subproblem_chunk",
                                                  0) or 0):
            raise ValueError(
                "scenario_source='streamed'/'synthesized' requires "
                "subproblem_chunk: the chunked hot loop is the "
                "streaming consumer (doc/streaming.md)")
        if not streaming:
            self.c = ship_stacked(b.c, t)
            self.c_stage = ship_stacked(b.c_stage, t)
            self.P_diag = jnp.asarray(b.P_diag, t)
        else:
            # set after the source builds (shared-structure check
            # first); P_diag/c_stage stay host-only — the chunk loop
            # broadcasts the shared P row per chunk, and the stage-
            # split cost consumers (EF/lshaped/cross-scenario) are
            # outside the streaming v1 surface (loud None failures)
            self.c = None
            self.c_stage = None
            self.P_diag = None
        self.c0 = jnp.asarray(b.c0, t)
        self.c0_stage = jnp.asarray(b.c0_stage, t)
        self.nonant_idx = jnp.asarray(b.nonant_idx)
        # shared-structure detection: when every scenario carries the SAME
        # constraint matrix and quadratic (only c/l/u/lb/ub differ — true
        # for uc/sizes/sslp/hydro where randomness enters the rhs), store A
        # and P unbatched so the kernel factors ONE (n, n) KKT matrix for
        # the whole batch (see ops/qp_solver.py module docstring). This is
        # the representation that reaches the reference's 1000-scenario
        # north star (ref. paperruns/larger_uc/1000scenarios_wind).
        A_np, P_np = np.asarray(b.A), np.asarray(b.P_diag)
        if A_np.ndim == 2:
            # batch already carries ONE shared matrix (ir/batch.py
            # compaction or the vector_patch fast path); the kernel's
            # shared mode additionally needs a shared quadratic
            self.shared_structure = bool((P_np == P_np[0]).all())
            if not self.shared_structure:
                raise ValueError(
                    "batch has a shared A but per-scenario P_diag — "
                    "the QP kernel's shared mode needs both (broadcast "
                    "A to (S, m, n) upstream for per-scenario quads)")
        else:
            self.shared_structure = bool(
                b.S > 1 and (A_np == A_np[0]).all()
                and (P_np == P_np[0]).all())
        if self.shared_structure:
            A2d = A_np if A_np.ndim == 2 else A_np[0]
            split = str(self.options.get("subproblem_precision",
                                         "")) == "df32"
            if split and t != jnp.float64:
                # big-instance df32: A lives on device ONLY as the
                # two-term f32 split (see ops/qp_solver.SplitMatrix) —
                # no f64 copy in HBM, no emulated-f64 matmul ever
                raise ValueError("subproblem_precision='df32' needs "
                                 "dtype=float64 (enable x64)")
            # per-batch device cache: every in-process cylinder of a
            # wheel builds an engine over the SAME host batch — without
            # sharing, each would put its own copy of the (m, n)
            # matrix (and, via ph._get_factors, its own scaled split)
            # in HBM, which at reference-UC scale OOMs the chip at
            # wheel width 3. jax arrays are immutable, so sharing is
            # safe; mesh runs bypass the cache (placement differs).
            # mesh runs must neither create NOR read the cache: cached
            # arrays carry single-device placement from a prior
            # non-mesh engine over the same batch object
            cache = getattr(b, "_dev_cache", None) if mesh is None \
                else None
            if cache is None and mesh is None:
                cache = b._dev_cache = {}
            if cache is not None:
                # cylinder threads hit the cache concurrently (engines
                # build factors lazily on their first solve); without a
                # lock each would build its own multi-GB device copy
                # before any setdefault landed — the OOM the cache
                # exists to prevent. dict.setdefault is atomic, so one
                # lock object wins and all threads share it.
                import threading
                lock = cache.setdefault("_lock", threading.Lock())

            def cached(key, fn):
                if cache is None:
                    return fn()
                with lock:
                    if key not in cache:
                        cache[key] = fn()
                    return cache[key]

            A_dev = cached(("A", str(t), split),
                           lambda: ship_shared_matrix(A2d, t, split=split))
            P_dev = jnp.asarray(P_np[0], t)
        else:
            cached = lambda key, fn: fn()
            A_dev = jnp.asarray(A_np, t)
            P_dev = self.P_diag
        if streaming:
            if not self.shared_structure:
                raise ValueError(
                    "scenario_source='streamed'/'synthesized' requires "
                    "a shared-structure batch (one A/P across "
                    "scenarios — the representation the chunked "
                    "single-factor loop streams over; models with "
                    "per-scenario matrices keep scenario_source="
                    "'resident'. farmer's synth family shares A: "
                    "stream.synth.synth_batch / doc/streaming.md)")
            from ..stream.source import make_source
            self._stream_source = make_source(b, self.options, t,
                                              mesh=mesh)
            # EXACT 2-row setup surrogates (stream/source.py module
            # docstring): qp_setup consumes the full-width vectors
            # only through all-scenario eq patterns + the cost-scale
            # max, so factors come out bit-identical to the resident
            # path's — without the (S, m)/(S, n) residency
            l2, u2, lb2, ub2, c2 = \
                self._stream_source.setup_arrays(t)
            self.c = c2
            self.qp_data = QPData(P_dev, A_dev, l2, u2, lb2, ub2)
        else:
            self.qp_data = QPData(
                P_dev, A_dev,
                cached(("l", str(t)), lambda: ship_stacked(b.l, t)),
                cached(("u", str(t)), lambda: ship_stacked(b.u, t)),
                cached(("lb", str(t)), lambda: ship_stacked(b.lb, t)),
                cached(("ub", str(t)), lambda: ship_stacked(b.ub, t)))
        # per-stage membership matrices for nonant reductions
        self.memberships = [jnp.asarray(b.tree.membership(s + 1), t)
                            for s in range(b.tree.num_stages - 1)]
        self.slot_slices = b.stage_slot_slices
        # (start, stop) twin of slot_slices for static jit arguments
        # (see compute_xbar)
        self.slot_bounds = tuple((sl.start, sl.stop)
                                 for sl in b.stage_slot_slices)
        # >1-device meshes: the explicit-collective scenario-axis ops
        # (segment-sum over tree-node index + psum per stage, sharded
        # chunk staging — parallel/mesh.ShardedScenarioOps). Single
        # device (or no mesh): None, and reductions keep the dense
        # membership-matmul spelling.
        self._shard_ops = None
        if mesh is not None and int(mesh.devices.size) > 1:
            from ..parallel.mesh import ShardedScenarioOps
            self._shard_ops = ShardedScenarioOps(
                mesh, b.tree, self.slot_bounds, b.S)

        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            from ..parallel.mesh import scenario_sharding

            def shard(a):
                if obs.enabled():
                    # the ONE deliberate device_put of a sharded run:
                    # the initial shard placement of the batch tensors.
                    # Steady-state iterations must add NOTHING to this
                    # counter (doc/sharding.md placement contract)
                    from ..obs.resource import put_nbytes
                    obs.counter_add(
                        "xfer.device_put_bytes",
                        put_nbytes(a, lambda leaf: scenario_sharding(
                            mesh, leaf.ndim)))
                return jax.device_put(a, scenario_sharding(mesh, a.ndim))
            # replicate per LEAF: a packed SplitMatrix mixes ranks
            # (dense (m, n) + index vectors), so one container-rank
            # spec would reject the rank-1 leaves
            repl = lambda a: jax.tree.map(
                lambda leaf: jax.device_put(
                    leaf,
                    NamedSharding(mesh, PartitionSpec(*([None] * leaf.ndim)))),
                a)
            self.prob = shard(self.prob)
            if self.vprob is not None:
                self.vprob = shard(self.vprob)
            self.c0 = shard(self.c0)
            self.c0_stage = shard(self.c0_stage)
            if not streaming:
                self.c = shard(self.c)
                self.c_stage = shard(self.c_stage)
                self.P_diag = shard(self.P_diag)
                # shared (unbatched) fields replicate; batched fields
                # shard on the scenario axis
                batched_ndim = dict(P_diag=2, A=3, l=2, u=2, lb=2, ub=2)
                self.qp_data = QPData(**{
                    k: (shard(a) if a.ndim == batched_ndim[k]
                        else repl(a))
                    for k, a in self.qp_data._asdict().items()})
            else:
                # streamed engines carry 2-row setup SURROGATES, not
                # per-scenario data — they replicate like every other
                # shared operand (the real per-scenario blocks arrive
                # per chunk with the chunk-row sharding, placed by the
                # source itself)
                self.c = repl(self.c)
                self.qp_data = QPData(**{
                    k: repl(a) for k, a in self.qp_data._asdict().items()})
            self.memberships = [shard(B) for B in self.memberships]

    def close_stream(self):
        """Shut the scenario source's prefetch machinery down
        (idempotent; restartable — the next chunked pass re-binds).
        Wired into hub finalize and the SIGTERM preemption path so a
        streamed wheel never hangs on a blocked producer thread."""
        if self._stream_source is not None:
            self._stream_source.close()

    # ---- reductions (the reference's Allreduce family) ----
    def Eobjective(self, obj_per_scen):
        """Probability-weighted expected objective (ref. phbase.py:279)."""
        return jnp.dot(self.prob, obj_per_scen)

    def scenario_objectives(self, x):
        """Per-scenario objective values for a (S, n) solution block."""
        if self._stream_source is not None:
            raise RuntimeError(
                "scenario_objectives needs the full-width cost block, "
                "which a streamed/synthesized scenario source never "
                "ships (doc/streaming.md v1 scope) — the chunked hot "
                "loop's per-chunk objectives cover the PH surface")
        quad = 0.5 * jnp.sum(self.P_diag * x * x, axis=-1)
        return quad + jnp.sum(self.c * x, axis=-1) + self.c0

    @property
    def xbar_weights(self):
        """(S,) scenario probabilities, or (S, K) per-variable weights."""
        return self.prob if self.vprob is None else self.vprob

    def compute_xbar(self, xn):
        """See the module-level compute_xbar (single implementation of
        the math); sharded engines run the collective segment-sum
        spelling instead (one psum per stage — parallel/mesh)."""
        if self._shard_ops is not None:
            return self._shard_ops.xbar(self.xbar_weights, xn)
        return compute_xbar(self.memberships, self.slot_slices,
                            self.xbar_weights, xn)

    def nonants_of(self, x):
        return x[..., self.nonant_idx]

    # ---- reporting (ref. spbase.py:516-576) ----
    def gather_var_values(self, x):
        """Host-side dict {var_name: (S, size) ndarray}."""
        xh = np.asarray(x)
        return {name: xh[:, sl] for name, sl in self.batch.template.var_slices.items()}

    def report_var_values(self, x, max_rows=20):
        vals = self.gather_var_values(x)
        for name, arr in vals.items():
            print(f"{name}: shape {arr.shape}")
            print(arr[:max_rows])
