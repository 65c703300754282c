"""Layer B: jaxpr-level audit of traced entry points.

``trace_and_check(fn, *args)`` traces ``fn`` with ``jax.make_jaxpr`` and
walks the jaxpr (recursing through pjit / shard_map / scan / cond
sub-jaxprs) enforcing:

- **collective axes** — every collective primitive (``psum``,
  ``all_gather``, ``reduce_scatter``, ``all_to_all``, ``ppermute``,
  ``axis_index``, ...) names only axes bound by the surrounding
  ``shard_map`` mesh, and every bound axis is one of the canonical names
  from :mod:`deepspeed_tpu.utils.groups`. When the global
  :class:`MeshTopology` is initialized, shard_map meshes must agree with
  its axis sizes — a mis-sized private mesh silently changes the collective
  group.
- **donation** — donated buffers must be aliasable to an output
  (shape+dtype match; XLA otherwise drops the donation and the "saving" is
  imaginary), and large state buffers that flow through unchanged-shape to
  an output but are NOT donated get flagged: that is the classic
  doubled-peak-HBM accumulator.
- **retrace hazards** — ``check_retrace`` counts distinct trace signatures
  over representative input sets; more signatures than expected means every
  step pays a recompile.

All checks emit the same structured :class:`Finding` records as Layer A, so
baselines, suppression accounting, and the CLI treat both layers uniformly.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from .findings import Finding, SEVERITY_ERROR, SEVERITY_WARNING, sort_findings
from .registry import LAYER_JAXPR, Rule, register

UNBOUND_AXIS = register(Rule(
    rule_id="unbound-collective-axis", layer=LAYER_JAXPR, severity=SEVERITY_ERROR,
    description="Collective names an axis not bound by the surrounding "
                "shard_map mesh",
    fix_hint="run the collective inside a shard_map whose mesh declares the "
             "axis, or fix the axis argument"))

NON_CANONICAL_AXIS = register(Rule(
    rule_id="non-canonical-axis", layer=LAYER_JAXPR, severity=SEVERITY_ERROR,
    description="Collective/mesh/sharding uses an axis name outside the "
                "canonical topology (utils/groups.MESH_AXES)",
    fix_hint="name mesh axes from deepspeed_tpu.utils.groups constants; "
             "private ad-hoc axis names fragment the collective groups"))

TOPOLOGY_MISMATCH = register(Rule(
    rule_id="topology-mismatch", layer=LAYER_JAXPR, severity=SEVERITY_ERROR,
    description="shard_map mesh axis size disagrees with the global "
                "MeshTopology — the collective group is not the configured one",
    fix_hint="build shard_maps over topology.mesh (runtime/topology.py), "
             "never over a locally constructed mesh"))

DONATION_UNUSABLE = register(Rule(
    rule_id="donation-unusable", layer=LAYER_JAXPR, severity=SEVERITY_WARNING,
    description="Donated buffer has no shape/dtype-matching output to alias; "
                "XLA drops the donation silently",
    fix_hint="donate only buffers that are replaced by a same-shaped output "
             "(state trees); drop the donate_argnums entry otherwise"))

UNDONATED_ACCUMULATOR = register(Rule(
    rule_id="undonated-accumulator", layer=LAYER_JAXPR, severity=SEVERITY_WARNING,
    description="Large input buffer with a matching output is not donated — "
                "input and output copies coexist at peak",
    fix_hint="add the argument to donate_argnums so XLA aliases the buffers "
             "in place"))

RETRACE_HAZARD = register(Rule(
    rule_id="retrace-hazard", layer=LAYER_JAXPR, severity=SEVERITY_WARNING,
    description="Representative inputs produce more distinct trace "
                "signatures than expected — each one is a full recompile",
    fix_hint="pad/bucket shapes to a fixed set and keep non-array arguments "
             "static and hashable"))

HOST_CALLBACK_IN_GRAPH = register(Rule(
    rule_id="host-callback-in-graph", layer=LAYER_JAXPR,
    severity=SEVERITY_ERROR,
    description="Host-callback primitive (pure_callback/io_callback/debug "
                "callback) inside an audited step graph — stalls the XLA "
                "pipeline per invocation and breaks the telemetry "
                "zero-overhead contract",
    fix_hint="keep observability host-side (telemetry span hooks around the "
             "dispatch); remove the callback from traced code"))

TELEMETRY_GRAPH_DRIFT = register(Rule(
    rule_id="telemetry-graph-drift", layer=LAYER_JAXPR,
    severity=SEVERITY_ERROR,
    description="Enabling telemetry changed a step entry point's jaxpr — "
                "the disabled/enabled paths must compile the identical "
                "program (telemetry is host-side by contract)",
    fix_hint="move the instrumentation outside the jit boundary; spans wrap "
             "dispatches, they never enter traced code"))

GUARDIAN_GRAPH_DRIFT = register(Rule(
    rule_id="guardian-graph-drift", layer=LAYER_JAXPR,
    severity=SEVERITY_ERROR,
    description="A guardian-OFF engine's step jaxpr differs from the "
                "pre-guardian program — the zero-overhead contract "
                "(docs/RESILIENCE.md): with the guardian disabled the "
                "sentinels must leave no trace in the step; armed, the "
                "anomaly word may only ride reductions the step already "
                "computes",
    fix_hint="keep the sentinel pack behind the spike_thresh=None gate in "
             "_apply_from_grads; policy/rollback logic stays host-side"))

# primitives that call back into Python from inside the compiled program
_CALLBACK_PRIMS = {"pure_callback", "io_callback", "debug_callback",
                   "callback"}

# jaxpr primitive names that carry a mesh-axis parameter ('axes' on psum/
# pmin/pmax, 'axis_name' on the rest — reduce_scatter is psum_scatter's
# primitive name).
_COLLECTIVE_PRIMS = {
    "psum", "psum_invariant", "pmin", "pmax", "ppermute", "pshuffle",
    "all_gather", "all_gather_invariant", "all_to_all", "reduce_scatter",
    "axis_index", "pgather",
}


def _canonical_axes() -> Tuple[str, ...]:
    from ..utils.groups import MESH_AXES
    return MESH_AXES


def _eqn_axes(eqn) -> Tuple[str, ...]:
    axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if isinstance(axes, str):
        return (axes,)
    return tuple(a for a in axes if isinstance(a, str))


def _sub_jaxprs(eqn) -> Iterable[Any]:
    for v in eqn.params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for item in vs:
            core = getattr(item, "jaxpr", None)
            if core is not None and hasattr(core, "eqns"):
                yield core            # ClosedJaxpr
            elif hasattr(item, "eqns") and hasattr(item, "invars"):
                yield item            # raw Jaxpr


def _mesh_axis_sizes(mesh) -> Dict[str, int]:
    shape = getattr(mesh, "shape", None)
    if shape is None:
        return {}
    return dict(shape)


class JaxprAuditor:
    def __init__(self, name: str, canonical: Optional[Sequence[str]] = None,
                 topology_sizes: Optional[Dict[str, int]] = None):
        self.name = name
        self.canonical = tuple(canonical) if canonical is not None else _canonical_axes()
        if topology_sizes is None:
            from ..runtime import topology as topo
            topology_sizes = (dict(topo.get_topology().mesh.shape)
                              if topo.is_initialized() else {})
        self.topology_sizes = topology_sizes
        self.findings: List[Finding] = []

    def _emit(self, rule: Rule, message: str) -> None:
        self.findings.append(Finding(
            rule_id=rule.rule_id, path=f"<trace:{self.name}>", line=0,
            severity=rule.severity, message=message, fix_hint=rule.fix_hint))

    def _check_mesh(self, mesh, where: str) -> Tuple[str, ...]:
        sizes = _mesh_axis_sizes(mesh)
        for axis, size in sizes.items():
            if axis not in self.canonical:
                self._emit(NON_CANONICAL_AXIS,
                           f"{where} mesh declares non-canonical axis "
                           f"{axis!r} (canonical: {self.canonical})")
            want = self.topology_sizes.get(axis)
            if want is not None and want != size:
                self._emit(TOPOLOGY_MISMATCH,
                           f"{where} mesh has {axis!r} size {size}, global "
                           f"topology has {want}")
        return tuple(sizes)

    def _check_spec_axes(self, spec, where: str) -> None:
        for entry in spec or ():
            entries = entry if isinstance(entry, (tuple, list)) else (entry,)
            for a in entries:
                if isinstance(a, str) and a not in self.canonical:
                    self._emit(NON_CANONICAL_AXIS,
                               f"{where} PartitionSpec uses non-canonical "
                               f"axis {a!r}")

    def walk(self, jaxpr, bound: Tuple[str, ...] = ()) -> None:
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            if prim == "shard_map":
                mesh = eqn.params.get("mesh")
                mesh_axes = self._check_mesh(mesh, "shard_map")
                auto = eqn.params.get("auto") or frozenset()
                inner_bound = tuple(set(bound) | (set(mesh_axes) - set(auto)))
                for sub in _sub_jaxprs(eqn):
                    self.walk(sub, inner_bound)
                continue
            if prim == "sharding_constraint":
                sharding = eqn.params.get("sharding")
                spec = getattr(sharding, "spec", None)
                if spec is not None:
                    self._check_spec_axes(spec, "with_sharding_constraint")
                mesh = getattr(sharding, "mesh", None)
                if mesh is not None:
                    self._check_mesh(mesh, "with_sharding_constraint")
            if prim in _CALLBACK_PRIMS:
                self._emit(HOST_CALLBACK_IN_GRAPH,
                           f"{prim} primitive inside the audited graph")
            if prim in _COLLECTIVE_PRIMS:
                for axis in _eqn_axes(eqn):
                    if axis not in bound:
                        self._emit(UNBOUND_AXIS,
                                   f"{prim} over axis {axis!r} which is not "
                                   f"bound here (bound: {sorted(bound)})")
                    elif axis not in self.canonical:
                        self._emit(NON_CANONICAL_AXIS,
                                   f"{prim} over non-canonical axis {axis!r}")
            for sub in _sub_jaxprs(eqn):
                self.walk(sub, bound)


# ---------------------------------------------------------------------------
# donation
# ---------------------------------------------------------------------------

def _aval_key(aval) -> Tuple:
    return (tuple(getattr(aval, "shape", ())), str(getattr(aval, "dtype", "")))


def _aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", ())
    dtype = getattr(aval, "dtype", None)
    itemsize = np.dtype(dtype).itemsize if dtype is not None else 0
    return int(np.prod(shape, dtype=np.int64)) * itemsize if shape else itemsize


def check_donation(name: str, closed_jaxpr, arg_leaf_counts: Sequence[int],
                   donate_argnums: Sequence[int],
                   big_bytes: int = 1 << 20) -> List[Finding]:
    """Audit donation against the traced jaxpr.

    ``arg_leaf_counts[i]`` is the number of flat invars argument ``i``
    contributed (pytree leaves); ``donate_argnums`` are fn-level argument
    indices, exactly as passed to ``jax.jit``.
    """
    findings: List[Finding] = []
    jaxpr = closed_jaxpr.jaxpr
    in_avals = [v.aval for v in jaxpr.invars]
    out_avals = [v.aval for v in jaxpr.outvars]

    # map argnum -> slice of flat invars
    offsets = np.cumsum([0] + list(arg_leaf_counts))
    donated = set()
    for argnum in donate_argnums:
        donated.update(range(offsets[argnum], offsets[argnum + 1]))

    # greedy aval matching: donated inputs claim outputs first (that is the
    # aliasing XLA will attempt), then undonated-large inputs look for
    # leftovers.
    free_out: Dict[Tuple, int] = {}
    for aval in out_avals:
        k = _aval_key(aval)
        free_out[k] = free_out.get(k, 0) + 1

    def claim(aval) -> bool:
        k = _aval_key(aval)
        if free_out.get(k, 0) > 0:
            free_out[k] -= 1
            return True
        return False

    for i in sorted(donated):
        if i >= len(in_avals):
            continue
        aval = in_avals[i]
        if not claim(aval):
            findings.append(Finding(
                rule_id=DONATION_UNUSABLE.rule_id, path=f"<trace:{name}>",
                line=0, severity=DONATION_UNUSABLE.severity,
                message=f"donated input #{i} {_aval_key(aval)} has no "
                        "matching output to alias — donation is dropped",
                fix_hint=DONATION_UNUSABLE.fix_hint))

    for i, aval in enumerate(in_avals):
        if i in donated or _aval_bytes(aval) < big_bytes:
            continue
        if claim(aval):
            findings.append(Finding(
                rule_id=UNDONATED_ACCUMULATOR.rule_id, path=f"<trace:{name}>",
                line=0, severity=UNDONATED_ACCUMULATOR.severity,
                message=f"input #{i} {_aval_key(aval)} "
                        f"({_aval_bytes(aval)} B) has a matching output but "
                        "is not donated — peak HBM holds both copies",
                fix_hint=UNDONATED_ACCUMULATOR.fix_hint))
    return findings


# ---------------------------------------------------------------------------
# retrace signatures
# ---------------------------------------------------------------------------

def trace_signature(args: Sequence[Any], kwargs: Optional[Dict] = None) -> Tuple:
    """Hashable abstraction of one call's signature: pytree structure +
    (shape, dtype) per array leaf, literal value per static leaf — the same
    identity jit uses to decide whether to retrace."""
    import jax

    leaves, treedef = jax.tree.flatten((tuple(args), kwargs or {}))
    sig = []
    for leaf in leaves:
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            sig.append(("array", tuple(leaf.shape), str(leaf.dtype)))
        else:
            sig.append(("static", repr(leaf)))
    return (str(treedef), tuple(sig))


def check_retrace(name: str, arg_sets: Sequence[Sequence[Any]],
                  max_signatures: int = 1) -> List[Finding]:
    sigs = {trace_signature(args) for args in arg_sets}
    if len(sigs) <= max_signatures:
        return []
    return [Finding(
        rule_id=RETRACE_HAZARD.rule_id, path=f"<trace:{name}>", line=0,
        severity=RETRACE_HAZARD.severity,
        message=f"{len(arg_sets)} representative input sets produce "
                f"{len(sigs)} distinct trace signatures "
                f"(expected <= {max_signatures}) — each is a recompile",
        fix_hint=RETRACE_HAZARD.fix_hint)]


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def trace_and_check(fn, *args, name: Optional[str] = None,
                    donate_argnums: Sequence[int] = (),
                    big_bytes: int = 1 << 20,
                    canonical: Optional[Sequence[str]] = None,
                    topology_sizes: Optional[Dict[str, int]] = None,
                    **kwargs) -> List[Finding]:
    """Trace ``fn(*args, **kwargs)`` and run the full jaxpr audit.

    ``args`` may be concrete arrays or ``jax.ShapeDtypeStruct`` trees —
    nothing is executed, only traced.
    """
    import jax

    name = name or getattr(fn, "__name__", "fn")
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    auditor = JaxprAuditor(name, canonical=canonical,
                           topology_sizes=topology_sizes)
    auditor.walk(closed.jaxpr)
    leaf_counts = [len(jax.tree.leaves(a)) for a in args]
    findings = auditor.findings + check_donation(
        name, closed, leaf_counts, donate_argnums, big_bytes=big_bytes)
    return sort_findings(findings)
