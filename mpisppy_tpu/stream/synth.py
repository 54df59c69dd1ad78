"""Device-side scenario synthesis for randomness-in-rhs families.

The third :class:`~mpisppy_tpu.stream.source.ScenarioSource` kind:
instead of shipping S full vector blocks H2D (or holding them in HBM),
a seeded jitted generator manufactures each scenario's rhs/bound
perturbations IN-KERNEL from ``(seed, scenario_id)`` — chunk staging
becomes pure device compute and the steady-state
``xfer.device_put_bytes`` of a synthesized wheel is ZERO.

The :class:`SynthSpec` is the SINGLE SOURCE of the family's scenario
data: the resident/streamed twins used by the equivalence tests are
built by materializing the SAME generator on host
(:func:`materialize`, jax's threefry PRNG is bit-identical across
backends), so synthesized == resident is exact by construction — not a
tolerance accident. The bits DO follow jax's threefry mode
(``jax_threefry_partitionable``, default on since jax 0.5): a family's
data is a function of (seed, scenario id, that mode), so a jax upgrade
that flips the mode draws a different — equally valid — instance
family; nothing in the program pins the mode.

Contract for ``SynthSpec.fn`` (model modules export it through
``scenario_synth_spec``, e.g. models/farmer.py, models/uc.py):

- pure jax, ``fn(key) -> tuple`` of per-field value arrays in
  ``fields`` order (``key`` is already folded with the scenario id:
  ``fold_in(PRNGKey(seed), scenario_id)`` — chunk composition can
  never change a scenario's data);
- fields address rhs/bound vectors only (``l``/``u``/``lb``/``ub``):
  cost randomness would have to track the per-stage cost split
  (ir/batch's ``c_stage`` consistency rule) and is rejected at spec
  construction;
- the spec must cover EVERY scenario-dependent entry of the family —
  the template (scenario 0's creator output) provides all remaining
  data, shared across scenarios.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import numpy as np

# fields a synth spec may perturb: rhs rows and variable boxes.
# Deliberately NOT "c" (see module docstring).
SYNTH_FIELDS = ("l", "u", "lb", "ub")
# the per-scenario vector fields a scenario source serves (superset of
# SYNTH_FIELDS: c rides along as a template-shared block)
SOURCE_FIELDS = ("l", "u", "lb", "ub", "c")


@dataclass(frozen=True)
class SynthField:
    """One perturbed block: ``field[start:stop]`` of the stacked
    vector (offsets from the template StandardForm's con_slices /
    var_slices)."""
    field: str
    start: int
    stop: int

    def __post_init__(self):
        if self.field not in SYNTH_FIELDS:
            raise ValueError(
                f"synth specs may perturb {SYNTH_FIELDS} only; got "
                f"{self.field!r} (cost randomness needs the c_stage "
                "split and is not supported)")
        if not (0 <= self.start < self.stop):
            raise ValueError(f"bad synth block [{self.start}, {self.stop})")


@dataclass(frozen=True)
class SynthSpec:
    """Seeded generator + the field layout it writes."""
    seed: int
    fields: tuple          # tuple[SynthField, ...]
    fn: Callable           # fn(folded_key) -> tuple of (stop-start,) arrays


def synth_values(spec: SynthSpec, scen_ids):
    """Per-scenario perturbation values for ``scen_ids`` (any int
    array): vmap of the spec's generator over
    ``fold_in(PRNGKey(seed), id)``. Pure jax — callers trace it into
    their chunk staging jit."""
    import jax
    import jax.numpy as jnp

    key0 = jax.random.PRNGKey(spec.seed)

    def one(s):
        vals = spec.fn(jax.random.fold_in(key0, s))
        if not isinstance(vals, tuple):
            vals = (vals,)
        return vals

    return jax.vmap(one)(jnp.asarray(scen_ids, jnp.int32))


def materialize(spec: SynthSpec, S: int, batch_rows: int = 8192) -> dict:
    """Host materialization of the generator's values for scenarios
    [0, S): ``{field: [(start, stop, (S, w) ndarray), ...]}``. Runs the
    SAME jitted generator the device source traces (threefry is
    backend-deterministic), in id batches so only one batch of values
    is transient at a time."""
    import jax

    fn = jax.jit(lambda ids: synth_values(spec, ids))
    parts = {f.field: [] for f in spec.fields}
    stacks = [[] for _ in spec.fields]
    for lo in range(0, S, batch_rows):
        ids = np.arange(lo, min(lo + batch_rows, S), dtype=np.int32)
        vals = fn(ids)
        for i, v in enumerate(vals):
            stacks[i].append(np.asarray(v, np.float64))
    for f, st in zip(spec.fields, stacks):
        parts[f.field].append((f.start, f.stop, np.concatenate(st)))
    return parts


def _validate_spec(spec: SynthSpec, widths: dict):
    """Check the declared blocks fit their field vectors and the
    generator's output arity/shapes match — at build time, not as a
    deep shape error inside the chunk jit."""
    import jax

    for f in spec.fields:
        w = widths[f.field]
        if f.stop > w:
            raise ValueError(
                f"synth block {f.field}[{f.start}:{f.stop}] exceeds the "
                f"field width {w}")
    shapes = jax.eval_shape(
        lambda ids: synth_values(spec, ids), np.zeros(2, np.int32))
    if not isinstance(shapes, tuple):
        shapes = (shapes,)
    if len(shapes) != len(spec.fields):
        raise ValueError(
            f"synth fn returns {len(shapes)} arrays for "
            f"{len(spec.fields)} declared fields")
    for f, sh in zip(spec.fields, shapes):
        if tuple(sh.shape) != (2, f.stop - f.start):
            raise ValueError(
                f"synth fn output for {f.field}[{f.start}:{f.stop}] has "
                f"per-scenario shape {tuple(sh.shape)[1:]}, block needs "
                f"({f.stop - f.start},)")


def synth_batch(scenario_creator, tree, spec_builder, creator_kwargs=None,
                seed: int = 0, materialize_values: bool = True,
                num_stages=None):
    """Build a (ScenarioBatch, SynthSpec) pair for a synth family: the
    creator runs ONCE (scenario 0 → shared template, like the
    vector_patch fast path) and the spec defines every scenario's
    perturbations — including scenario 0's, so the family's data is
    identical whether it runs resident, streamed, or synthesized.

    ``materialize_values=True`` stacks real (S, ...) host arrays (the
    resident / streamed representation). ``materialize_values=False``
    keeps the batch vectors as zero-stride ``np.broadcast_to`` VIEWS of
    the template (a synthesized-source engine never reads them — its
    data comes from the generator; the views only carry shape), so an
    S=1M batch costs no host memory beyond the template."""
    from ..ir.batch import ScenarioBatch, _nonant_indexing
    from ..ir.standard_form import lower

    creator_kwargs = creator_kwargs or {}
    T = num_stages or tree.num_stages
    f0 = lower(scenario_creator(tree.scen_names[0], **creator_kwargs),
               num_stages=T)
    spec = spec_builder(f0, seed=seed, **creator_kwargs)
    S = len(tree.scen_names)
    widths = {"l": f0.m, "u": f0.m, "lb": f0.n, "ub": f0.n}
    _validate_spec(spec, widths)

    base = {"c": f0.c, "l": f0.l, "u": f0.u, "lb": f0.lb, "ub": f0.ub,
            "c_stage": f0.c_stage, "P_diag": f0.P_diag}
    if materialize_values:
        vecs = {k: np.repeat(np.asarray(v, np.float64)[None], S, axis=0)
                for k, v in base.items()}
        for fname, blocks in materialize(spec, S).items():
            for start, stop, vals in blocks:
                vecs[fname][:, start:stop] = vals
    else:
        vecs = {k: np.broadcast_to(np.asarray(v, np.float64),
                                   (S,) + np.shape(v))
                for k, v in base.items()}

    nonant_idx, nonant_stage, slot_slices = _nonant_indexing(f0, tree)
    batch = ScenarioBatch(
        tree=tree, template=f0,
        c=vecs["c"], c0=np.full(S, np.float64(f0.c0)),
        P_diag=vecs["P_diag"],
        A=f0.A,                               # ONE shared matrix
        l=vecs["l"], u=vecs["u"], lb=vecs["lb"], ub=vecs["ub"],
        c_stage=vecs["c_stage"],
        c0_stage=np.repeat(np.asarray(f0.c0_stage,
                                      np.float64)[None], S, axis=0),
        prob=tree.probabilities.copy(),
        nonant_idx=nonant_idx, nonant_stage=nonant_stage,
        stage_slot_slices=slot_slices,
    )
    return batch, spec
