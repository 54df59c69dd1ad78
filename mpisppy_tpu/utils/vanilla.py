"""Config -> hub/spoke construction dicts (the vanilla analog).

Mirrors mpisppy/utils/vanilla.py:30-408: canned factories that turn the
validated RunConfig into the hub/spoke dict schema spin_the_wheel
consumes, one factory per cylinder kind. Every cylinder gets its OWN
engine over its own batch (the reference's cylinders each own an opt
object the same way, ref. sputils.py:99-108).
"""

from __future__ import annotations

from .. import obs
from .config import RunConfig, SpokeConfig

_DTYPES = {"float32": "float32", "f32": "float32",
           "float64": "float64", "f64": "float64"}


def _pop_dtype(options):
    """Extract an optional per-cylinder "dtype" option ("float32"/"f64"/…)
    into an engine dtype kwarg — e.g. an f32 hub for hot-loop speed with
    f64 bound spokes for certified tightness in the same wheel."""
    name = options.pop("dtype", None)
    if name is None:
        return {}
    import jax.numpy as jnp
    return {"dtype": getattr(jnp, _DTYPES[str(name)])}


def build_batch_for(cfg: RunConfig):
    """Model registry: name -> stacked batch (+ bundling). Models that
    export ``scenario_vector_patch`` get the structure-shared fast path
    (ir/batch.py build_batch(vector_patch=...)) automatically — at
    reference-UC scale that is the difference between one template
    lowering and S of them."""
    from ..ir.batch import build_batch
    from .. import models

    mod = getattr(models, cfg.model)
    kwargs = dict(cfg.model_kwargs)
    if cfg.model in ("hydro", "ccopf"):
        # the creator decodes scenario numbers with the SAME branching the
        # tree was built with — they must never diverge, whether the user
        # passed the branching under tree_kwargs or directly in
        # model_kwargs. Merge both into one source of truth.
        tk = dict(kwargs.pop("tree_kwargs", {}))
        bkey = "branching" if cfg.model == "ccopf" else "branching_factors"
        if bkey in kwargs:
            if bkey in tk and tuple(tk[bkey]) != tuple(kwargs[bkey]):
                raise ValueError(
                    f"{cfg.model}: {bkey} given in both model_kwargs and "
                    "tree_kwargs with different values")
            tk.setdefault(bkey, kwargs[bkey])
        tree = mod.make_tree(**tk)
        kwargs.update(tk)
    else:
        tree = mod.make_tree(cfg.num_scens)
    if cfg.algo.scenario_source == "synthesized":
        # synthesized scenario source (mpisppy_tpu/stream,
        # doc/streaming.md): the model's synth spec is the single
        # source of the family's data — the creator runs once for the
        # shared template, the batch vectors are zero-stride broadcast
        # VIEWS of it (an S=1M batch costs no host memory), and the
        # engine manufactures the per-scenario rhs perturbations
        # in-kernel. The spec rides the batch to hub_dict, which
        # forwards it as the ``synth_spec`` engine option.
        if not hasattr(mod, "scenario_synth_spec"):
            raise ValueError(
                f"scenario_source='synthesized' needs model "
                f"{cfg.model!r} to export scenario_synth_spec "
                "(doc/streaming.md; farmer and uc do)")
        if cfg.num_bundles:
            raise ValueError("bundling merges scenario blocks and is "
                             "not supported with a synthesized "
                             "scenario source")
        from ..stream.synth import synth_batch
        seed = int(kwargs.pop("synth_seed", 0))
        batch, spec = synth_batch(
            mod.scenario_creator, tree, mod.scenario_synth_spec,
            creator_kwargs=kwargs, seed=seed, materialize_values=False)
        batch._synth_spec = spec
        obs.event("batch.build", {"model": cfg.model, "S": batch.S,
                                  "K": batch.K, "n": batch.n,
                                  "shared_A": True,
                                  "scenario_source": "synthesized"})
        return batch
    batch = build_batch(mod.scenario_creator, tree, creator_kwargs=kwargs,
                        vector_patch=getattr(mod, "scenario_vector_patch",
                                             None))
    if cfg.num_bundles:
        from ..core.bundles import form_bundles
        batch = form_bundles(batch, cfg.num_bundles)
    obs.event("batch.build", {"model": cfg.model, "S": batch.S,
                              "K": batch.K, "n": batch.n,
                              "shared_A": bool(batch.shared_A)})
    return batch


def ckpt_fingerprint(cfg: RunConfig) -> str:
    """The run-identity fingerprint stamped into checkpoint bundles
    (ckpt/bundle.config_fingerprint): a bundle only resumes into a
    wheel with the same model family, scenario count, model kwargs,
    bundling, and hub algorithm — anything else would install
    foreign (or shape-mismatched) state."""
    from ..ckpt.bundle import config_fingerprint
    return config_fingerprint({
        "model": cfg.model, "num_scens": cfg.num_scens,
        "model_kwargs": cfg.model_kwargs,
        "num_bundles": cfg.num_bundles, "hub": cfg.hub})


def hub_dict(cfg: RunConfig, batch=None):
    """ref. vanilla.py:54 ph_hub (+ aph/lshaped variants). ``batch``:
    optionally a prebuilt batch shared across cylinders (engines never
    mutate the host arrays; wheel_dicts passes one build to all)."""
    from ..core.ph import PH
    from ..core.aph import APH
    from ..core.lshaped import LShapedMethod
    from ..core.cross_scenario import CrossScenarioPH
    from ..cylinders.hub import PHHub, APHHub, LShapedHub, CrossScenarioHub

    options = cfg.algo.to_options()
    options.update(cfg.hub_options)
    dtype_kw = _pop_dtype(options)
    hub_kwargs = {"options": {}}
    if cfg.rel_gap is not None:
        hub_kwargs["options"]["rel_gap"] = cfg.rel_gap
    if cfg.abs_gap is not None:
        hub_kwargs["options"]["abs_gap"] = cfg.abs_gap
    if cfg.wheel_deadline is not None:
        hub_kwargs["options"]["wheel_deadline"] = cfg.wheel_deadline
    if cfg.status_port is not None:
        # the hub process owns the live status server (obs/live.py)
        hub_kwargs["options"]["status_port"] = cfg.status_port
        hub_kwargs["options"]["status_host"] = cfg.status_host
    if "crossed_bound_tol" in cfg.supervisor:
        hub_kwargs["options"]["crossed_bound_tol"] = \
            cfg.supervisor["crossed_bound_tol"]
    if cfg.checkpoint_dir or cfg.resume_from:
        # durable run-state checkpoints + resume (mpisppy_tpu.ckpt):
        # the hub owns capture; resume installs before iter 0. The
        # fingerprint makes a bundle from a different configuration
        # refuse cleanly at load.
        if cfg.checkpoint_dir:
            hub_kwargs["options"]["checkpoint_dir"] = cfg.checkpoint_dir
            hub_kwargs["options"]["checkpoint_interval"] = \
                cfg.checkpoint_interval
            hub_kwargs["options"]["checkpoint_keep"] = cfg.checkpoint_keep
        if cfg.resume_from:
            hub_kwargs["options"]["resume_from"] = cfg.resume_from
        hub_kwargs["options"]["checkpoint_fingerprint"] = \
            ckpt_fingerprint(cfg)

    cross = any(sp.kind == "cross_scenario" for sp in cfg.spokes)
    if cfg.hub == "ph":
        opt_cls, hub_cls = (CrossScenarioPH, CrossScenarioHub) if cross \
            else (PH, PHHub)
    elif cfg.hub == "aph":
        opt_cls, hub_cls = APH, APHHub
    else:
        opt_cls, hub_cls = LShapedMethod, LShapedHub
    opt_kwargs = {"batch": batch if batch is not None
                  else build_batch_for(cfg),
                  "options": options, **dtype_kw}
    spec = getattr(opt_kwargs["batch"], "_synth_spec", None)
    if spec is not None:
        # the synthesized source's generator (build_batch_for attached
        # it): an engine option rather than config — SynthSpec holds a
        # jax callable and cannot ride the jax-free config tree
        options["synth_spec"] = spec
    if cfg.mesh_devices is not None:
        if cfg.hub in ("ph", "aph") and not cross:
            # scenario-axis sharding for the hub engine
            # (doc/sharding.md): 0 = every visible device (the whole
            # slice — or the whole pod when
            # utils/runtime.maybe_init_distributed ran first)
            import jax

            from ..parallel.mesh import make_mesh
            n_vis = len(jax.devices())
            if cfg.mesh_devices > n_vis:
                # never a narrower mesh than the one asked for: a run
                # that believes it is on 4 chips and sits on 1 reports
                # numbers for the wrong machine
                raise ValueError(
                    f"mesh_devices={cfg.mesh_devices} exceeds the "
                    f"{n_vis} visible device(s) (multi-host runs need "
                    "the coordinator knob so jax sees the global set, "
                    "doc/sharding.md)")
            opt_kwargs["mesh"] = make_mesh(
                n_devices=cfg.mesh_devices or None)
        else:
            # the lshaped hub and the cross-scenario cut engine keep
            # the unsharded path (the cut store is not sharding-
            # audited) — say so instead of silently dropping the knob
            import warnings
            warnings.warn(
                f"mesh_devices is ignored for this wheel (hub="
                f"{cfg.hub!r}{', cross_scenario' if cross else ''}): "
                "scenario-axis sharding covers the ph/aph hubs only "
                "(doc/sharding.md)", RuntimeWarning, stacklevel=2)
    return {"hub_class": hub_cls, "hub_kwargs": hub_kwargs,
            "opt_class": opt_cls, "opt_kwargs": opt_kwargs}


def spoke_classes(kind: str):
    """(spoke_class, opt_class) for a spoke kind — importable without
    building any batch (the multi-process launcher sizes windows from
    the class alone)."""
    from ..core.ph import PHBase
    from ..core.fwph import FWPH
    from ..core.lshaped import LShapedMethod
    from ..cylinders.lagrangian_bounder import (LagrangianOuterBound,
                                                LagrangerOuterBound)
    from ..cylinders.xhat_bounders import (DiveInnerBound,
                                           XhatLooperInnerBound,
                                           XhatShuffleInnerBound,
                                           XhatSpecificInnerBound,
                                           XhatLShapedInnerBound)
    from ..cylinders.slam_heuristic import (SlamUpHeuristic,
                                            SlamDownHeuristic)
    from ..cylinders.fwph_spoke import FrankWolfeOuterBound
    from ..cylinders.cross_scen_spoke import CrossScenarioCutSpoke
    from ..cylinders.ef_bounder import EFMipBound

    return {
        "lagrangian": (LagrangianOuterBound, PHBase),
        "efmip": (EFMipBound, PHBase),
        "lagranger": (LagrangerOuterBound, PHBase),
        "xhatshuffle": (XhatShuffleInnerBound, PHBase),
        "xhatlooper": (XhatLooperInnerBound, PHBase),
        "xhatspecific": (XhatSpecificInnerBound, PHBase),
        "xhatlshaped": (XhatLShapedInnerBound, PHBase),
        "fwph": (FrankWolfeOuterBound, FWPH),
        "slamup": (SlamUpHeuristic, PHBase),
        "slamdown": (SlamDownHeuristic, PHBase),
        "cross_scenario": (CrossScenarioCutSpoke, LShapedMethod),
        # device-side batched incumbent search (doc/incumbents.md)
        "dive": (DiveInnerBound, PHBase),
    }[kind]


def spoke_dict(cfg: RunConfig, sp: SpokeConfig, batch=None):
    """ref. vanilla.py:95-408 — one factory per spoke kind."""
    spoke_cls, opt_cls = spoke_classes(sp.kind)
    options = cfg.algo.to_options()
    options.update(sp.options)
    # run-level spoke knobs (per-spoke options win): the typed config
    # replaces SPOKE_SLEEP_TIME monkeypatching in fast fault scenarios
    if cfg.spoke_sleep_time is not None:
        options.setdefault("spoke_sleep_time", cfg.spoke_sleep_time)
    if cfg.incumbent_mode is not None:
        # run-level incumbent source policy (doc/incumbents.md); only
        # the x̂-family spokes read it, and per-spoke options still win
        options.setdefault("incumbent_mode", cfg.incumbent_mode)
    dtype_kw = _pop_dtype(options)
    spoke_kwargs = {}
    if cfg.trace_prefix:
        spoke_kwargs["trace_prefix"] = cfg.trace_prefix
    return {"spoke_class": spoke_cls, "spoke_kwargs": spoke_kwargs,
            "opt_class": opt_cls,
            "opt_kwargs": {"batch": batch if batch is not None
                           else build_batch_for(cfg),
                           "options": options, **dtype_kw}}


def wheel_dicts(cfg: RunConfig):
    """The full (hub_dict, spoke_dicts) pair for spin_the_wheel. The
    batch is built ONCE and shared by every cylinder (engines read the
    host arrays, they never write them) — at reference-UC scale each
    template lowering costs ~a minute, so per-cylinder rebuilds would
    multiply a fixed cost by the wheel width."""
    cfg.validate()
    if cfg.algo.scenario_source != "resident" and cfg.spokes:
        # v1 scope (doc/streaming.md): spoke engines read full-width
        # batch arrays (incumbent pools, Lagrangian warm states) that
        # a streamed hub deliberately never ships — a streaming wheel
        # runs hub-only until the spoke surfaces are stream-audited
        raise ValueError(
            "scenario_source='streamed'/'synthesized' wheels are "
            "hub-only (doc/streaming.md v1 scope); drop the spokes or "
            "use scenario_source='resident'")
    obs.event("wheel.build", {"model": cfg.model,
                              "num_scens": cfg.num_scens,
                              "hub": cfg.hub,
                              "spokes": [sp.kind for sp in cfg.spokes]})
    batch = build_batch_for(cfg)
    spoke_ds = [spoke_dict(cfg, sp, batch=batch) for sp in cfg.spokes]
    if cfg.checkpoint_dir or cfg.resume_from:
        # per-spoke checkpoint/resume wiring needs the spoke INDEX
        # (file naming), which spoke_dict alone never sees; the
        # process launcher does the same injection per spawn
        # (utils/multiproc._spawn_one_spoke, generation-aware)
        from ..ckpt.spoke_state import spoke_resume_options
        for i, (sp, sd) in enumerate(zip(cfg.spokes, spoke_ds)):
            for k, v in spoke_resume_options(
                    cfg.checkpoint_dir, cfg.resume_from, i,
                    sp.kind).items():
                sd["opt_kwargs"]["options"].setdefault(k, v)
    return hub_dict(cfg, batch=batch), spoke_ds
