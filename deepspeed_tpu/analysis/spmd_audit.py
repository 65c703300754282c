"""Layer C: post-SPMD sharding & memory audit of compiled entry points.

Layers A and B stop at the source: the AST and the jaxpr. But the failures
that actually cap scale — GSPMD quietly materializing an all-gather around
a mis-sharded matmul, a logits tensor replicated across a sharded mesh, a
remat schedule stacking gathered params into residuals, a donation XLA
silently dropped, a step whose temp bytes crept past the HBM ceiling — only
exist in the *partitioned, optimized* artifact. This layer lowers each
registered :class:`~.entry_points.EntrySpec` with its real mesh/shardings
(via the shared :mod:`.lowering` path telemetry also uses) and audits the
compiled program:

- ``implicit-reshard`` — diff the collective *kinds* between the source
  jaxpr and the partitioned HLO. Kinds implied by the source's own
  collective primitives (psum -> all-reduce, ppermute ->
  collective-permute, ...) are expected, as are the kinds each spec
  *declares* GSPMD may insert (``expected_spmd`` — e.g. the engine step's
  data-parallel grad all-reduce). Anything else is the partitioner fixing
  up a sharding mismatch behind your back, reported with estimated bytes.
- ``replicated-large-intermediate`` — a non-parameter instruction in the
  partitioned program whose (dtype, shape) still equals a large *logical*
  value's full shape means every device materializes the whole tensor:
  replication (or a full re-gather) on a sharded mesh.
- ``remat-residual-full-param`` — the ZeRO schedule invariant "residuals
  must never contain full params" (docs/ZERO_OVERLAP.md), previously
  prose: scan residuals (stacked ``ys``) whose per-iteration slice matches
  a full parameter shape re-materialize the gathered weights once per
  layer. The pipelined prefetch CARRY legitimately holds one gathered
  layer; stacked residuals never may.
- ``dead-donation`` — the module-level ``input_output_alias`` table is
  what XLA *actually* aliased. A donated input absent from it wastes its
  bytes: the caller gave the buffer up and got nothing back. (Layer B's
  ``donation-unusable`` is the aval-matching prediction; this is the
  ground truth.)
- ``memory-budget-regression`` — ``memory_analysis()`` + collective bytes
  checked against the committed shrink-only ``tools/memory_budgets.json``
  (:mod:`.budgets`). Exceeding a budget is a hard finding; so is a
  registered entry point with no budget at all.

Findings carry the ``<spmd:NAME>`` path marker so the baseline machinery
(:mod:`.baseline`) treats the layer independently, exactly like Layer B's
``<trace:NAME>``.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, FrozenSet, Iterable, List, Optional, Set, Tuple

import numpy as np

from .budgets import KIND_PREFIX, TRACKED_FIELDS, tracks_field
from .findings import Finding, SEVERITY_ERROR, SEVERITY_WARNING, sort_findings
from .registry import LAYER_SPMD, Rule, register

SPMD_PREFIX = "<spmd:"

IMPLICIT_RESHARD = register(Rule(
    rule_id="implicit-reshard", layer=LAYER_SPMD, severity=SEVERITY_ERROR,
    description="Partitioner-inserted collective of a kind neither the "
                "source jaxpr nor the entry point's declared contract "
                "expects — GSPMD is resharding behind your back",
    fix_hint="fix the producer/consumer shardings so the operands agree "
             "(with_sharding_constraint or shard_map specs); if the "
             "collective is intended, declare the kind in the spec's "
             "expected_spmd contract"))

REPLICATED_LARGE = register(Rule(
    rule_id="replicated-large-intermediate", layer=LAYER_SPMD,
    severity=SEVERITY_WARNING,
    description="Compiled intermediate materializes a large logical value "
                "at FULL size on every device of a sharded mesh",
    fix_hint="shard the value (with_sharding_constraint over the batch/seq "
             "axes) or compute it blockwise; a fully-replicated tensor "
             "multiplies its HBM cost by the mesh size"))

REMAT_RESIDUAL_PARAM = register(Rule(
    rule_id="remat-residual-full-param", layer=LAYER_SPMD,
    severity=SEVERITY_ERROR,
    description="Scan residuals (stacked ys) hold full-parameter-shaped "
                "tensors — the backward saves gathered weights per layer "
                "instead of re-gathering",
    fix_hint="residuals must hold activations only: recompute the block "
             "from its saved input and re-gather params in the backward "
             "scan (docs/ZERO_OVERLAP.md, layer-granular remat)"))

DEAD_DONATION = register(Rule(
    rule_id="dead-donation", layer=LAYER_SPMD, severity=SEVERITY_WARNING,
    description="Donated input missing from the compiled module's "
                "input_output_alias table — XLA dropped the donation and "
                "the bytes are wasted",
    fix_hint="make the donated buffer flow to a same-shape/dtype/sharding "
             "output, or remove it from donate_argnums; Layer B's "
             "donation-unusable hint shows the aval mismatch"))

MEMORY_BUDGET_REGRESSION = register(Rule(
    rule_id="memory-budget-regression", layer=LAYER_SPMD,
    severity=SEVERITY_ERROR,
    description="Compiled memory/collective bytes exceed the committed "
                "shrink-only budget (tools/memory_budgets.json), or the "
                "entry point has no committed budget",
    fix_hint="shrink the program back under budget; if the growth is "
             "justified, raise the budget BY HAND in "
             "tools/memory_budgets.json and defend it in review"))

SPMD_LOWER_FAILED = register(Rule(
    rule_id="spmd-lower-failed", layer=LAYER_SPMD, severity=SEVERITY_ERROR,
    description="Entry point failed to lower/compile on the audit mesh — "
                "a broken hot path must not pass silently",
    fix_hint="run under JAX_PLATFORMS=cpu with "
             "xla_force_host_platform_device_count>=8 and fix the compile "
             "error"))

#: default thresholds (bytes). Overridable per call; the tiny audit models
#: sit far below both, so HEAD is clean by construction and the rules are
#: exercised by fixtures with lowered thresholds.
REPLICATED_BYTES_DEFAULT = 1 << 26        # 64 MiB full-size intermediate
RESIDUAL_BYTES_DEFAULT = 1 << 14          # 16 KiB per-layer residual slice

# source jaxpr collective primitive -> HLO collective kind(s) it may
# legitimately lower to (reduce_scatter may legalize as all-reduce+slice).
_SRC_PRIM_KINDS: Dict[str, Tuple[str, ...]] = {
    # JAX 0.9.0 binds the *_invariant forms under shard_map(check_vma=True)
    "psum": ("all-reduce",), "psum_invariant": ("all-reduce",),
    "pmin": ("all-reduce",), "pmax": ("all-reduce",),
    "all_gather": ("all-gather",), "all_gather_invariant": ("all-gather",),
    "pgather": ("all-gather",),
    "reduce_scatter": ("reduce-scatter", "all-reduce"),
    "ppermute": ("collective-permute",),
    "pshuffle": ("collective-permute",),
    "all_to_all": ("all-to-all",),
}

_HLO_COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                         "collective-permute", "all-to-all")

# HLO shape element type -> numpy dtype string (for byte math and for
# matching logical avals against compiled instruction shapes)
_HLO_DTYPES = {
    "pred": "bool", "s8": "int8", "s16": "int16", "s32": "int32",
    "s64": "int64", "u8": "uint8", "u16": "uint16", "u32": "uint32",
    "u64": "uint64", "f16": "float16", "bf16": "bfloat16", "f32": "float32",
    "f64": "float64", "f8e4m3fn": "float8_e4m3fn", "f8e5m2": "float8_e5m2",
    "c64": "complex64", "c128": "complex128",
}
_NP_TO_HLO = {v: k for k, v in _HLO_DTYPES.items()}

# one HLO instruction: `%name = <shape> opcode(...)` where <shape> is a
# typed array `f32[8,16]{1,0}` or a tuple of them.
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*"
    r"(\([^)]*\)|[a-z][\w]*\[[0-9,]*\](?:\{[^}]*\})?)\s+"
    r"([a-z][a-z0-9\-]*)\(", re.MULTILINE)
_ARRAY_SHAPE_RE = re.compile(r"([a-z][\w]*)\[([0-9,]*)\]")


def _dtype_itemsize(hlo_dtype: str) -> int:
    np_name = _HLO_DTYPES.get(hlo_dtype)
    if np_name is None:
        return 0
    if np_name.startswith("float8"):
        return 1
    if np_name == "bfloat16":
        return 2
    try:
        return np.dtype(np_name).itemsize
    except TypeError:
        return 0


def _parse_shapes(shape_text: str) -> List[Tuple[str, Tuple[int, ...], int]]:
    """'(f32[8,16]{1,0}, s32[4])' -> [(dtype, dims, bytes), ...]."""
    out = []
    for m in _ARRAY_SHAPE_RE.finditer(shape_text):
        dtype, dims_text = m.group(1), m.group(2)
        if dtype not in _HLO_DTYPES:
            continue  # token/opaque types
        dims = tuple(int(d) for d in dims_text.split(",")) if dims_text else ()
        n = int(np.prod(dims, dtype=np.int64)) if dims else 1
        out.append((dtype, dims, n * _dtype_itemsize(dtype)))
    return out


def iter_hlo_instructions(hlo_text: str) -> Iterable[
        Tuple[str, List[Tuple[str, Tuple[int, ...], int]]]]:
    """Yield ``(opcode, [(dtype, shape, bytes), ...])`` for every
    instruction in the optimized module (fused computations included —
    their bodies are listed like any other computation)."""
    for m in _INSTR_RE.finditer(hlo_text):
        yield m.group(2), _parse_shapes(m.group(1))


# a collective instruction with its operand list: opcode + everything up
# to (at least) the operand parenthesis; the blob is cut at the matching
# close paren by _operand_blob so trailing attributes never leak shapes in
_COLL_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*"
    r"(?:\([^)]*\)|[a-z][\w]*\[[0-9,]*\](?:\{[^}]*\})?)\s+"
    r"([a-z][a-z0-9\-]*)\((.*)$", re.MULTILINE)


def _operand_blob(rest: str) -> str:
    """``rest`` starts just past the opcode's '('; return the operand text
    up to the MATCHING ')' (tuple-shaped operands nest parens)."""
    depth = 1
    for i, ch in enumerate(rest):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                return rest[:i]
    return rest


def collective_summary(hlo_text: str) -> Dict[str, Tuple[int, int]]:
    """-> {kind: (count, total_operand_bytes)} over the partitioned program.

    Bytes are OPERAND-side (each launch's input payload) — the same
    convention as Layer D's per-launch ``moved_bytes`` and the runtime
    ledger's ``record_collective``, and the honest wire estimate under
    quantized transport: a reduce-scatter's input is what travels the
    links (its result is the 1/n shard), and an int8 all-to-all's input
    is the 1-byte payload + scale sideband. (Before ISSUE 8 this charged
    RESULT bytes, which inverted the reduce-scatter vs all-to-all
    comparison and hid the quantization win.) Async pairs count once
    (``-start`` carries the operands, ``-done`` is skipped)."""
    out: Dict[str, Tuple[int, int]] = {}
    for m in _COLL_RE.finditer(hlo_text):
        opcode = m.group(1)
        kind = opcode[:-6] if opcode.endswith("-start") else opcode
        if opcode.endswith("-done") or kind not in _HLO_COLLECTIVE_KINDS:
            continue
        shapes = _parse_shapes(_operand_blob(m.group(2)))
        count, total = out.get(kind, (0, 0))
        out[kind] = (count + 1, total + sum(b for _, _, b in shapes))
    return out


def parse_alias_params(hlo_text: str) -> Optional[Set[int]]:
    """Parameter numbers in the module's ``input_output_alias`` table —
    the donations XLA actually honored. None when the module declares no
    alias table at all (nothing was donated / backend elided it)."""
    marker = "input_output_alias={"
    start = hlo_text.find(marker)
    if start < 0:
        return None
    # the table nests braces ({0}: (0, {}, may-alias)) — scan for balance
    depth, i = 1, start + len(marker)
    while i < len(hlo_text) and depth:
        depth += {"{": 1, "}": -1}.get(hlo_text[i], 0)
        i += 1
    table = hlo_text[start + len(marker):i - 1]
    return {int(p) for p in re.findall(r":\s*\((\d+)\s*,", table)}


# ---------------------------------------------------------------------------
# jaxpr-side helpers
# ---------------------------------------------------------------------------

def _walk_jaxprs(jaxpr):
    yield jaxpr
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            items = v if isinstance(v, (tuple, list)) else (v,)
            for item in items:
                core = getattr(item, "jaxpr", None)
                if core is not None and hasattr(core, "eqns"):
                    yield from _walk_jaxprs(core)
                elif hasattr(item, "eqns") and hasattr(item, "invars"):
                    yield from _walk_jaxprs(item)


def source_collective_kinds(closed_jaxpr) -> Set[str]:
    """HLO collective kinds the source jaxpr's own primitives lower to."""
    kinds: Set[str] = set()
    for jaxpr in _walk_jaxprs(closed_jaxpr.jaxpr):
        for eqn in jaxpr.eqns:
            for k in _SRC_PRIM_KINDS.get(eqn.primitive.name, ()):
                kinds.add(k)
    return kinds


def _aval_nbytes(aval) -> int:
    shape = tuple(getattr(aval, "shape", ()))
    dtype = getattr(aval, "dtype", None)
    if dtype is None:
        return 0
    try:
        itemsize = np.dtype(dtype).itemsize
    except TypeError:
        itemsize = 2 if "bfloat16" in str(dtype) else 0
    n = int(np.prod(shape, dtype=np.int64)) if shape else 1
    return n * itemsize


def _hlo_key(aval) -> Optional[Tuple[str, Tuple[int, ...]]]:
    hlo_dtype = _NP_TO_HLO.get(str(getattr(aval, "dtype", "")))
    if hlo_dtype is None:
        return None
    return (hlo_dtype, tuple(getattr(aval, "shape", ())))


def large_logical_avals(closed_jaxpr, threshold: int
                        ) -> Dict[Tuple[str, Tuple[int, ...]], int]:
    """Full (logical) shapes of source values >= threshold bytes, keyed the
    way compiled HLO spells shapes."""
    out: Dict[Tuple[str, Tuple[int, ...]], int] = {}
    for jaxpr in _walk_jaxprs(closed_jaxpr.jaxpr):
        for eqn in jaxpr.eqns:
            for var in eqn.outvars:
                aval = getattr(var, "aval", None)
                if aval is None:
                    continue
                nbytes = _aval_nbytes(aval)
                if nbytes < threshold:
                    continue
                key = _hlo_key(aval)
                if key is not None:
                    out[key] = nbytes
    return out


def scan_param_residuals(closed_jaxpr,
                         param_shapes: FrozenSet[Tuple[Tuple[int, ...], str]],
                         min_bytes: int) -> List[Tuple[Tuple[int, ...], str, int]]:
    """Stacked scan outputs (ys) whose per-iteration slice matches a full
    parameter shape: ``[(stacked_shape, dtype, stacked_bytes), ...]``.
    Carries are exempt — the pipelined schedule's prefetch carry holds one
    gathered layer by design; residuals are what persists per layer."""
    hits = []
    for jaxpr in _walk_jaxprs(closed_jaxpr.jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name != "scan":
                continue
            num_carry = eqn.params.get("num_carry", 0)
            for var in eqn.outvars[num_carry:]:
                aval = getattr(var, "aval", None)
                shape = tuple(getattr(aval, "shape", ()))
                if len(shape) < 1:
                    continue
                slice_key = (shape[1:], str(getattr(aval, "dtype", "")))
                if slice_key in param_shapes:
                    nbytes = _aval_nbytes(aval)
                    if nbytes >= min_bytes:
                        hits.append((shape, slice_key[1], nbytes))
    return hits


# ---------------------------------------------------------------------------
# the audit
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SpmdReport:
    """Per-entry numbers the budget flow and ``--json`` consume."""
    name: str
    memory: Dict[str, float]
    collective_counts: Dict[str, int]
    collective_bytes: int
    collective_bytes_by_kind: Dict[str, int] = dataclasses.field(
        default_factory=dict)

    def budget_fields(self) -> Dict[str, int]:
        out = {f: int(self.memory[f]) for f in TRACKED_FIELDS
               if f in self.memory}
        out["collective_bytes"] = int(self.collective_bytes)
        # per-kind shrink-only budgets (ISSUE 8): the static pin of the
        # quantized-transport byte reduction, one key per HLO kind
        for kind, nbytes in sorted(self.collective_bytes_by_kind.items()):
            out[KIND_PREFIX + kind] = int(nbytes)
        return out

    def to_dict(self) -> Dict[str, Any]:
        return {"name": self.name, "memory": self.memory,
                "collective_counts": self.collective_counts,
                "collective_bytes": self.collective_bytes,
                "collective_bytes_by_kind": dict(
                    sorted(self.collective_bytes_by_kind.items()))}


def _finding(rule: Rule, name: str, message: str) -> Finding:
    return Finding(rule_id=rule.rule_id, path=f"{SPMD_PREFIX}{name}>",
                   line=0, severity=rule.severity, message=message,
                   fix_hint=rule.fix_hint)


def audit_artifact(spec, artifact, *,
                   replicated_bytes: int = REPLICATED_BYTES_DEFAULT,
                   residual_bytes: int = RESIDUAL_BYTES_DEFAULT,
                   ) -> Tuple[List[Finding], SpmdReport]:
    """All compiled-layer rules except the budget check (which needs the
    committed file — :func:`check_budgets`)."""
    import jax

    name = spec.name
    findings: List[Finding] = []
    hlo = artifact.hlo_text

    # --- implicit-reshard -------------------------------------------------
    expected = source_collective_kinds(artifact.closed_jaxpr) | set(
        spec.expected_spmd)
    summary = collective_summary(hlo)
    for kind in sorted(set(summary) - expected):
        count, nbytes = summary[kind]
        findings.append(_finding(
            IMPLICIT_RESHARD, name,
            f"partitioner inserted {count} {kind} instruction(s) "
            f"(~{nbytes} B/device result bytes); source jaxpr implies "
            f"{sorted(expected) or 'no collectives'}"))

    # --- replicated-large-intermediate ------------------------------------
    if jax.device_count() > 1:
        large = large_logical_avals(artifact.closed_jaxpr, replicated_bytes)
        if large:
            seen: Dict[Tuple[str, Tuple[int, ...]], int] = {}
            for opcode, shapes in iter_hlo_instructions(hlo):
                if opcode in ("parameter", "constant"):
                    continue
                for dtype, dims, _ in shapes:
                    key = (dtype, dims)
                    if key in large:
                        seen[key] = seen.get(key, 0) + 1
            for (dtype, dims), count in sorted(seen.items()):
                findings.append(_finding(
                    REPLICATED_LARGE, name,
                    f"{dtype}{list(dims)} ({large[(dtype, dims)]} B) appears "
                    f"at FULL logical size in {count} compiled "
                    f"instruction(s) on a {jax.device_count()}-device mesh "
                    f"— replicated, not sharded"))

    # --- remat-residual-full-param ----------------------------------------
    if spec.param_shapes:
        for shape, dtype, nbytes in scan_param_residuals(
                artifact.closed_jaxpr, spec.param_shapes, residual_bytes):
            findings.append(_finding(
                REMAT_RESIDUAL_PARAM, name,
                f"scan residual stacks full-parameter slices: "
                f"{dtype}{list(shape)} ({nbytes} B) — gathered weights "
                f"saved once per layer"))

    # --- dead-donation ----------------------------------------------------
    offsets = np.cumsum([0] + list(artifact.arg_leaf_counts))
    donated: List[int] = []
    for argnum in artifact.donate_argnums:
        donated.extend(range(offsets[argnum], offsets[argnum + 1]))
    if donated:
        aliased = parse_alias_params(hlo)
        kept = _kept_param_numbers(artifact)
        invars = artifact.closed_jaxpr.jaxpr.invars
        for i in donated:
            param_no = kept.get(i) if kept is not None else i
            if param_no is None:
                # the executable pruned the arg entirely: donated AND unused
                ok = False
            else:
                ok = aliased is not None and param_no in aliased
            if not ok:
                nbytes = _aval_nbytes(invars[i].aval) if i < len(invars) else 0
                findings.append(_finding(
                    DEAD_DONATION, name,
                    f"donated input leaf #{i} was not aliased by XLA "
                    f"({nbytes} B wasted — buffer surrendered for "
                    "nothing)"))

    report = SpmdReport(
        name=name, memory=artifact.memory() or {},
        collective_counts={k: c for k, (c, _) in summary.items()},
        collective_bytes=sum(b for _, b in summary.values()),
        collective_bytes_by_kind={k: b for k, (_, b) in summary.items()})
    return findings, report


def _kept_param_numbers(artifact) -> Optional[Dict[int, Optional[int]]]:
    """flat invar index -> compiled parameter number, accounting for XLA
    dropping unused args (kept_var_idx). None = mapping unavailable
    (assume identity)."""
    kept = None
    for path in ("_executable", "runtime_executable"):
        ex = getattr(artifact.compiled, path, None)
        if ex is not None and hasattr(ex, "_kept_var_idx"):
            kept = sorted(ex._kept_var_idx)
            break
    if kept is None:
        return None
    mapping: Dict[int, Optional[int]] = {}
    pos = {idx: n for n, idx in enumerate(kept)}
    n_invars = len(artifact.closed_jaxpr.jaxpr.invars)
    for i in range(n_invars):
        mapping[i] = pos.get(i)
    return mapping


def check_budgets(name: str, report: SpmdReport,
                  budgets: Optional[Dict]) -> List[Finding]:
    """Diff one entry's report against the committed budgets (already
    loaded + env-matched by the caller; pass None to skip)."""
    if budgets is None:
        return []
    entry = budgets.get("budgets", {}).get(name)
    if entry is None:
        return [_finding(
            MEMORY_BUDGET_REGRESSION, name,
            "no committed budget in tools/memory_budgets.json — run "
            "`dstpu lint --update-budgets` and commit the file")]
    findings = []
    current = report.budget_fields()
    for field in sorted(current):
        if not tracks_field(field, TRACKED_FIELDS):
            continue
        if field not in entry:
            if field.startswith(KIND_PREFIX) and current[field] > 0:
                # a collective KIND with no committed budget appeared —
                # the per-kind analogue of a new-entry missing budget
                findings.append(_finding(
                    MEMORY_BUDGET_REGRESSION, name,
                    f"{field} {current[field]} B has no committed per-kind "
                    f"budget — a new collective kind entered the compiled "
                    f"program (hand-add it with review, or fix the "
                    f"sharding)"))
            continue
        if current[field] > entry[field]:
            findings.append(_finding(
                MEMORY_BUDGET_REGRESSION, name,
                f"{field} {current[field]} B exceeds committed budget "
                f"{entry[field]} B (+{current[field] - entry[field]} B)"))
    return findings


def audit_spec_spmd(spec, budgets: Optional[Dict] = None, **thresholds
                    ) -> Tuple[List[Finding], Optional[SpmdReport]]:
    """Lower+compile one spec and run every Layer-C rule. A spec that
    cannot compile is itself a hard finding."""
    from .lowering import lower_entry

    try:
        with spec.mesh_ctx():
            artifact = lower_entry(spec.fn, spec.args,
                                   donate_argnums=spec.donate_argnums,
                                   jit_kwargs=spec.jit_kwargs,
                                   name=spec.name)
    except Exception as e:  # noqa: BLE001 — any compile failure is a finding
        return [_finding(SPMD_LOWER_FAILED, spec.name,
                         f"failed to lower/compile: "
                         f"{type(e).__name__}: {e}")], None
    findings, report = audit_artifact(spec, artifact, **thresholds)
    findings += check_budgets(spec.name, report, budgets)
    return findings, report


def iter_compiled_entries(names=None):
    """Build + lower/compile each registered entry point ONCE, yielding
    ``(name, spec, artifact, error)`` — ``error`` is a message string when
    the spec could not even build or compile (spec/artifact None as
    appropriate). Layers C and D both consume this, so a combined run
    pays one compile per entry, not one per layer."""
    from deepspeed_tpu.runtime import topology as topo_mod

    from .entry_points import SPEC_BUILDERS, build_spec
    from .lowering import lower_entry

    if names:
        unknown = sorted(set(names) - set(SPEC_BUILDERS))
        if unknown:
            raise ValueError(
                f"unknown entry point(s): {', '.join(unknown)} "
                f"(known: {', '.join(sorted(SPEC_BUILDERS))})")
    try:
        for name in SPEC_BUILDERS:
            if names and name not in names:
                continue
            try:
                spec = build_spec(name)  # resets the global topology first
            except Exception as e:  # noqa: BLE001
                yield (name, None, None,
                       f"entry point failed to build: "
                       f"{type(e).__name__}: {e}")
                continue
            try:
                with spec.mesh_ctx():
                    artifact = lower_entry(
                        spec.fn, spec.args,
                        donate_argnums=spec.donate_argnums,
                        jit_kwargs=spec.jit_kwargs, name=spec.name)
            except Exception as e:  # noqa: BLE001
                yield (name, spec, None,
                       f"failed to lower/compile: {type(e).__name__}: {e}")
                continue
            yield name, spec, artifact, None
    finally:
        topo_mod.reset()


def audit_spmd_entry_points(names=None, budgets: Optional[Dict] = None,
                            entries=None,
                            ) -> Tuple[List[Finding], Dict[str, SpmdReport]]:
    """Run Layer C over the registered entry points (default: all).

    ``budgets`` is the loaded+env-matched budgets dict (None skips budget
    checks — the CLI and gate pass it when the environment matches the
    committed mesh). ``entries`` is an optional pre-materialized
    :func:`iter_compiled_entries` result — a combined ``--spmd
    --schedule`` run compiles once and feeds both layers. Returns
    findings plus per-entry reports for ``--update-budgets`` /
    ``--json``."""
    findings: List[Finding] = []
    reports: Dict[str, SpmdReport] = {}
    for name, spec, artifact, error in (
            entries if entries is not None else iter_compiled_entries(names)):
        if error is not None:
            findings.append(_finding(SPMD_LOWER_FAILED, name, error))
            continue
        f, report = audit_artifact(spec, artifact)
        f += check_budgets(name, report, budgets)
        findings.extend(f)
        reports[name] = report
    return sort_findings(findings), reports
