"""Pass 3 — kernel-plan lint: block plans and generated source, statically.

``kernels/tuning.block_plans`` is the export surface the autotuner embeds in
calibrated-profile JSON; serving trusts those numbers when it launches Pallas
kernels. This pass re-derives every claim a plan makes — block divisibility,
grid bounds, VMEM footprints — against a :class:`HardwareProfile`, so a plan
that would OOM VMEM or mis-tile is rejected *offline*, without compiling a
kernel.

The same pass lints the Deployment Module's generated source
(``core/codegen._emit_source``) at the AST level: the emitted combines are
machine-written Python, and the historical failure mode (PR 4: coefficient
magnitudes silently dropped) is a *generator* bug — so the lint independently
re-checks the emitted linear combinations against the scheme's coefficient
tensors instead of trusting the emitter.
"""
from __future__ import annotations

import ast
import builtins

import numpy as np

from repro.core.lcma import LCMA
from repro.core.hardware import HardwareProfile
from .findings import ERROR, WARNING, Finding

__all__ = ["lint_block_plan", "lint_scheme_plans", "lint_quant_plans",
           "lint_workload", "lint_codegen", "BACKEND_DTYPES",
           "MAX_GRID_PROGRAMS"]

PASS = "plan-lint"
CODEGEN_PASS = "codegen-lint"

# Legal element dtypes per execution backend. The Pallas TPU pipeline has no
# float64 path (MXU is bf16/int8; VPU f32), and the quantized kernels only
# accept int8 operands with f32 scales.
BACKEND_DTYPES = {
    "jnp": {"float64", "float32", "bfloat16", "float16", "int8"},
    "pallas": {"float32", "bfloat16", "int8"},
    "pallas_interpret": {"float32", "bfloat16", "int8"},
    "shard_map_local": {"float32", "bfloat16"},
}

# Pallas grids are int32-indexed; stay far below the wrap-around point.
MAX_GRID_PROGRAMS = 2 ** 31 - 1


def _check_div(findings, subject, what, num, den):
    if den <= 0 or num % den != 0:
        findings.append(Finding(
            PASS, ERROR, subject,
            f"{what}: block {den} does not divide dimension {num}"))
        return False
    return True


def lint_block_plan(plan: dict, hw: HardwareProfile, *,
                    dtype: str = "float32", backend: str = "pallas",
                    subject: str | None = None) -> list[Finding]:
    """Statically check one ``block_plans`` dict against a hardware profile."""
    import jax.numpy as jnp
    from repro.kernels import tuning

    findings: list[Finding] = []
    subject = subject or f"plan<{plan.get('grid')};R={plan.get('R')}>"

    required = ("grid", "R", "padded_shape", "combine_a", "combine_b",
                "fused_gemm", "combine_a_vmem_bytes", "combine_b_vmem_bytes",
                "fused_gemm_vmem_bytes", "vmem_budget_bytes")
    missing = [k for k in required if k not in plan]
    if missing:
        return [Finding(PASS, ERROR, subject,
                        f"malformed plan: missing keys {missing}")]

    m, k, n = (int(x) for x in plan["grid"])
    R = int(plan["R"])
    Mp, Kp, Np = (int(x) for x in plan["padded_shape"])

    # dtype legality per backend
    allowed = BACKEND_DTYPES.get(backend)
    if allowed is None:
        findings.append(Finding(PASS, WARNING, subject,
                                f"unknown backend {backend!r}: dtype legality "
                                f"not checked"))
    elif str(dtype) not in allowed:
        findings.append(Finding(
            PASS, ERROR, subject,
            f"dtype {dtype} is not executable on backend {backend!r} "
            f"(legal: {sorted(allowed)})"))

    # grid divisibility of the padded problem
    for name, dim, g in (("M", Mp, m), ("K", Kp, k), ("N", Np, n)):
        if g < 1 or dim % g != 0:
            findings.append(Finding(
                PASS, ERROR, subject,
                f"padded {name}={dim} is not divisible by grid {g}"))
    if any(f.is_error for f in findings):
        return findings   # partition sizes below would be meaningless

    X, Ks, Z = Mp // m, Kp // k, Np // n
    bax, bay = (int(x) for x in plan["combine_a"])
    bbx, bby = (int(x) for x in plan["combine_b"])
    fx, fz, fy = (int(x) for x in plan["fused_gemm"])

    ok = True
    ok &= _check_div(findings, subject, "combine_a.x over M/m", X, bax)
    ok &= _check_div(findings, subject, "combine_a.y over K/k", Ks, bay)
    ok &= _check_div(findings, subject, "combine_b.x over K/k", Ks, bbx)
    ok &= _check_div(findings, subject, "combine_b.y over N/n", Z, bby)
    ok &= _check_div(findings, subject, "fused_gemm.x over M/m", X, fx)
    ok &= _check_div(findings, subject, "fused_gemm.z over N/n", Z, fz)
    ok &= _check_div(findings, subject, "fused_gemm.y over K/k", Ks, fy)

    # grid bounds (programs are int32-indexed)
    if ok:
        n_prog = max((X // fx) * (Z // fz) * (Ks // fy),
                     (X // bax) * (Ks // bay), (Ks // bbx) * (Z // bby))
        if n_prog > MAX_GRID_PROGRAMS:
            findings.append(Finding(
                PASS, ERROR, subject,
                f"kernel grid has {n_prog} programs > int32 bound "
                f"{MAX_GRID_PROGRAMS}"))

    # VMEM: recompute from the blocks (don't trust the reported numbers),
    # cross-check the report, then compare against budget AND profile.
    it = jnp.dtype(dtype).itemsize
    recomputed = {
        "combine_a_vmem_bytes": tuning.combine_vmem(bax, bay, R, m * k, it),
        "combine_b_vmem_bytes": tuning.combine_vmem(bbx, bby, R, k * n, it),
        "fused_gemm_vmem_bytes": tuning.fused_gemm_vmem(fx, fz, fy, R, m, n, it),
    }
    budget = int(plan["vmem_budget_bytes"])
    for key, want in recomputed.items():
        got = int(plan[key])
        if got != want:
            findings.append(Finding(
                PASS, ERROR, subject,
                f"{key} reports {got} but the blocks imply {want} "
                f"(stale or hand-edited plan)"))
        stage_budget = min(budget, hw.vmem_bytes)
        if want > stage_budget:
            findings.append(Finding(
                PASS, ERROR, subject,
                f"{key.removesuffix('_vmem_bytes')} VMEM footprint {want} B "
                f"exceeds the {'profile' if want > hw.vmem_bytes else 'plan'} "
                f"limit {stage_budget} B ({hw.name}: {hw.vmem_bytes} B)"))

    # MXU alignment: advisory — misaligned tiles run, at reduced utilization.
    # Only flagged when an aligned divisor actually exists: a block must tile
    # the dimension exactly, and a multiple of mxu_align divides dim only if
    # mxu_align itself does.
    if ok:
        for name, b, dim in (("fused_gemm.x", fx, X), ("fused_gemm.z", fz, Z)):
            if dim % hw.mxu_align == 0 and b % hw.mxu_align != 0:
                findings.append(Finding(
                    PASS, WARNING, subject,
                    f"{name} block {b} is not a multiple of the MXU dimension "
                    f"{hw.mxu_align} (dim {dim} allows an aligned tile)"))
    return findings


def lint_scheme_plans(l: LCMA, shapes, hw: HardwareProfile, *,
                      dtype: str = "float32",
                      backend: str = "pallas") -> list[Finding]:
    """Generate and lint the block plans scheme ``l`` would use on ``shapes``."""
    from repro.kernels import tuning
    findings: list[Finding] = []
    for (M, K, N) in shapes:
        plan = tuning.block_plans(l, M, K, N, dtype=dtype, hw=hw)
        findings.extend(lint_block_plan(
            plan, hw, dtype=dtype, backend=backend,
            subject=f"{l.name}@{M}x{K}x{N}/{dtype}"))
    return findings


def lint_workload(arch, hw: HardwareProfile, *, batch: int = 8,
                  seq: int = 512, dtype: str | None = None,
                  backend: str = "pallas", train: bool = False,
                  quantize: bool = False, mesh_shape=None,
                  all_candidates: bool = False) -> list[Finding]:
    """Statically lint an architecture's full contraction set against ``hw``.

    The workload registry (``core.workloads``) enumerates every planned
    contraction ``arch`` issues at (batch, seq); for each unique contraction
    shape, the Decision Module picks its scheme and that scheme's block plan
    is linted (divisibility, grid bounds, VMEM vs the profile) — the same
    checks serving trusts at launch, run offline without compiling a kernel.
    ``all_candidates=True`` lints EVERY candidate scheme per shape instead
    (a scheme the decision would never pick may legitimately fail there,
    e.g. an int32 grid overflow on a huge lm_head — useful for triage, not
    for CI gating). With ``quantize=True`` the int8 pipeline of each
    weight-static contraction is linted too. ``arch`` is a registry id /
    paper workload name or a ``ModelConfig``.
    """
    from repro.core import algorithms, decision
    from repro.core.workloads import resolve_contractions, _resolve_arch
    from repro.kernels import tuning

    cfg = _resolve_arch(arch)
    name = getattr(cfg, "name", str(arch))
    dtype = str(dtype or getattr(cfg, "dtype", "bfloat16"))
    findings: list[Finding] = []
    for c in resolve_contractions(arch, batch, seq, train=train,
                                  mesh_shape=mesh_shape):
        if quantize and not (c.weight_static and c.kind in
                             ("dense", "grouped_moe")):
            continue
        m, k, n = c.shape
        if all_candidates:
            schemes = list(algorithms.candidates())
        else:
            d = decision.decide(m, n, k, hw, dtype)
            schemes = [d.algo] if d.use_lcma else []
        for l in schemes:
            plan = tuning.block_plans(l, m, k, n, dtype=dtype, hw=hw)
            findings.extend(lint_block_plan(
                plan, hw, dtype=dtype, backend=backend,
                subject=f"{name}:{c.role}:{l.name}@{m}x{k}x{n}/{dtype}"))
            if quantize:
                findings.extend(lint_quant_plans(
                    l, [(m, k, n)], hw, backend=backend))
    return findings


def _snap_block(dim: int, cap: int = 128) -> int:
    """Largest divisor of ``dim`` that is <= ``cap`` (the kernels' snap rule)."""
    return next(d for d in range(min(cap, dim), 0, -1) if dim % d == 0)


def lint_quant_plans(l: LCMA, shapes, hw: HardwareProfile, *,
                     backend: str = "pallas",
                     acc_bits: int = 32) -> list[Finding]:
    """Statically lint the int8-quantized pipeline ``l`` would run on ``shapes``.

    Re-derives, for each serving shape, exactly the choices the quantized
    PlannedWeight path makes — the weight scale-block ``by`` (largest divisor
    of the combined K that is <= 128, per ``engine._quantize_weight``) and the
    fused kernel's divisor-snapped ``(bx, bz)`` — then checks the claims those
    kernels assert at trace time, without compiling anything:

    * **backend legality** — int8 operands must be executable on ``backend``
      (``shard_map_local`` has no quant path);
    * **accumulator safety** — a ``by``-deep int8*int8 reduction must fit the
      int-``acc_bits`` accumulator (``stability.max_safe_accum_depth``);
    * **scale-block / grid divisibility** — ``by | K/k``, ``bx | M/m``,
      ``bz | N/n``: the asserts ``fused_gemm_combine_h_quant`` and
      ``quantize_b_blockwise`` make on every launch;
    * **grid bounds** — the quant GEMM grid stays below the int32 program
      index wrap-around;
    * **degenerate scale blocks** (warning) — a ``by`` far below the 128 cap
      means the shape's combined K is oddly factored and the per-block scale
      arrays bloat the memory traffic the decision tier priced.
    """
    from repro.analysis.stability import int8_accum_bound, max_safe_accum_depth
    from repro.kernels import tuning

    findings: list[Finding] = []
    allowed = BACKEND_DTYPES.get(backend)
    safe_depth = max_safe_accum_depth(acc_bits)

    for (M, K, N) in shapes:
        subject = f"{l.name}@{M}x{K}x{N}/int8"

        if allowed is None:
            findings.append(Finding(
                PASS, WARNING, subject,
                f"unknown backend {backend!r}: int8 legality not checked"))
        elif "int8" not in allowed:
            findings.append(Finding(
                PASS, ERROR, subject,
                f"int8 is not executable on backend {backend!r} "
                f"(legal: {sorted(allowed)}); the quantized tier must not "
                f"be selected here"))
            continue

        # the int8 pipeline pads each part's rows and columns to int8
        # tiles (kernels/ops.falcon_matmul_pallas_quant); K/k stays as is
        Ks = -(-K // l.k)
        X = tuning.round_up(-(-M // l.m), tuning.sublane("int8"))
        Z = tuning.round_up(-(-N // l.n), tuning.LANE)
        by = _snap_block(Ks)
        bx = tuning.snap_block(X, tuning.sublane("int8"))
        bz = tuning.snap_block(Z, tuning.LANE)

        ok = True
        ok &= _check_div(findings, subject, "quant scale block over K/k", Ks, by)
        ok &= _check_div(findings, subject, "quant fused_gemm.x over M/m", X, bx)
        ok &= _check_div(findings, subject, "quant fused_gemm.z over N/n", Z, bz)
        if not ok:
            continue

        if by > safe_depth:
            findings.append(Finding(
                PASS, ERROR, subject,
                f"int8 reduction depth {by} can overflow the int{acc_bits} "
                f"accumulator: worst-case |sum| = {int8_accum_bound(by)} > "
                f"{2 ** (acc_bits - 1) - 1} (max safe depth {safe_depth})"))

        n_prog = (X // bx) * (Z // bz) * (Ks // by)
        if n_prog > MAX_GRID_PROGRAMS:
            findings.append(Finding(
                PASS, ERROR, subject,
                f"quant kernel grid has {n_prog} programs > int32 bound "
                f"{MAX_GRID_PROGRAMS}"))

        if Ks >= 32 and by < 32:
            findings.append(Finding(
                PASS, WARNING, subject,
                f"quant scale block snaps to {by} (combined K {Ks} has no "
                f"divisor in [32, 128]): scale arrays are {Ks // by}x larger "
                f"than the 128-block baseline the decision tier prices"))
    return findings


# ---------------------------------------------------------------------------
# Codegen AST lint
# ---------------------------------------------------------------------------

_ALLOWED_GLOBALS = {"jax", "jnp"} | set(dir(builtins))

_REQUIRED_FUNCS = ("combine_a", "combine_b", "gemm_stage", "combine_h",
                   "lcma_matmul")


class _FuncScope(ast.NodeVisitor):
    """Collect assigned and loaded names within one function body."""

    def __init__(self):
        self.stored: set[str] = set()
        self.loaded: list[tuple[str, int]] = []

    def visit_Name(self, node: ast.Name):
        if isinstance(node.ctx, ast.Store):
            self.stored.add(node.id)
        elif isinstance(node.ctx, ast.Load):
            self.loaded.append((node.id, node.lineno))
        self.generic_visit(node)

    def visit_FunctionDef(self, node: ast.FunctionDef):  # nested defs: opaque
        self.stored.add(node.name)


def _coeff_from_expr(expr: ast.expr, var_coeff: dict) -> None:
    """Accumulate ``{name: coeff}`` from an emitted linear combination.

    The emitter's grammar is tiny: sums/differences of ``name``,
    ``const * name`` and unary minus. Anything outside that grammar raises
    ``ValueError`` — which the caller reports as a lint error.
    """
    def term(e, sign):
        if isinstance(e, ast.UnaryOp) and isinstance(e.op, ast.USub):
            term(e.operand, -sign)
        elif isinstance(e, ast.BinOp) and isinstance(e.op, ast.Add):
            term(e.left, sign)
            term(e.right, sign)
        elif isinstance(e, ast.BinOp) and isinstance(e.op, ast.Sub):
            term(e.left, sign)
            term(e.right, -sign)
        elif isinstance(e, ast.BinOp) and isinstance(e.op, ast.Mult):
            try:  # literal_eval also accepts a negated constant (-3 * x)
                c = ast.literal_eval(e.left)
            except ValueError:
                raise ValueError(f"non-constant scale {ast.dump(e.left)}") from None
            name = _name_of(e.right)
            var_coeff[name] = var_coeff.get(name, 0) + sign * c
        elif isinstance(e, ast.Constant):
            if e.value != 0.0:
                raise ValueError(f"unexpected constant {e.value!r}")
        else:
            name = _name_of(e)
            var_coeff[name] = var_coeff.get(name, 0) + sign
    term(expr, 1)


def _name_of(e: ast.expr) -> str:
    if isinstance(e, ast.Name):
        return e.id
    if isinstance(e, ast.Subscript) and isinstance(e.value, ast.Name) \
            and isinstance(e.slice, ast.Constant):
        return f"{e.value.id}[{e.slice.value}]"
    raise ValueError(f"unexpected term {ast.dump(e)}")


def _expected_combine(coeff: np.ndarray, part: str, r: int) -> dict:
    d1, d2 = coeff.shape[1], coeff.shape[2]
    return {f"{part}_{i}_{l}": int(coeff[r, i, l])
            for i in range(d1) for l in range(d2) if coeff[r, i, l] != 0}


def lint_codegen(l: LCMA, options=None) -> list[Finding]:
    """AST-level checks on the source ``codegen._emit_source`` emits for ``l``.

    * the source parses and defines the full stage surface;
    * no function loads a name that is neither assigned locally, a parameter,
      a module-level def, nor an allowed global (``jax``/``jnp``/builtins) —
      the "sliced a_0_3 that was never emitted" class of generator bug;
    * every ``at_r = ...`` / ``bt_r = ...`` combine is parsed back into its
      ``{operand: coefficient}`` map and compared EXACTLY against U/V — a
      re-derivation, not a trust of the emitter (PR 4's magnitude-dropping
      bug is invisible to name-scope checks but caught here);
    * Combine-H subscripts ``H[r]`` stay within rank bounds and its
      coefficient map matches W.
    """
    from repro.core import codegen

    o = options or codegen.CodegenOptions()
    src = codegen._emit_source(l, o)
    subject = f"codegen:{l.name}"
    try:
        tree = ast.parse(src)
    except SyntaxError as e:
        return [Finding(CODEGEN_PASS, ERROR, subject,
                        f"emitted source does not parse: {e}")]
    findings: list[Finding] = []

    funcs = {node.name: node for node in tree.body
             if isinstance(node, ast.FunctionDef)}
    for name in _REQUIRED_FUNCS:
        if name not in funcs:
            findings.append(Finding(CODEGEN_PASS, ERROR, subject,
                                    f"generated source lacks def {name}()"))
    module_names = set(funcs) | _ALLOWED_GLOBALS

    for fname, node in funcs.items():
        scope = _FuncScope()
        for stmt in node.body:
            scope.visit(stmt)
        params = {a.arg for a in node.args.args}
        known = scope.stored | params | module_names
        for name, lineno in scope.loaded:
            if name not in known:
                findings.append(Finding(
                    CODEGEN_PASS, ERROR, subject,
                    f"{fname}() line {lineno}: loads undefined name {name!r}"))

    # Re-derive the combine coefficient maps from the AST.
    for fname, coeff, part, out in (("combine_a", l.U, "a", "at"),
                                    ("combine_b", l.V, "b", "bt")):
        node = funcs.get(fname)
        if node is None:
            continue
        got: dict[int, dict] = {}
        for stmt in node.body:
            if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                    and isinstance(stmt.targets[0], ast.Name):
                tgt = stmt.targets[0].id
                if tgt.startswith(out + "_"):
                    r = int(tgt[len(out) + 1:])
                    terms: dict = {}
                    try:
                        _coeff_from_expr(stmt.value, terms)
                    except ValueError as e:
                        findings.append(Finding(
                            CODEGEN_PASS, ERROR, subject,
                            f"{fname}() {tgt}: unparseable combine ({e})"))
                        continue
                    got[r] = {k: v for k, v in terms.items() if v != 0}
        if set(got) != set(range(l.R)):
            findings.append(Finding(
                CODEGEN_PASS, ERROR, subject,
                f"{fname}() emits combines for ranks {sorted(got)}; "
                f"expected 0..{l.R - 1}"))
        for r, terms in got.items():
            want = _expected_combine(coeff, part, r)
            if terms != want:
                findings.append(Finding(
                    CODEGEN_PASS, ERROR, subject,
                    f"{fname}() rank {r}: emitted coefficients {terms} != "
                    f"scheme tensor {want}"))

    # Combine-H: subscript bounds + coefficient map vs W.
    node = funcs.get("combine_h")
    if node is not None:
        for stmt in ast.walk(node):
            if isinstance(stmt, ast.Subscript) \
                    and isinstance(stmt.value, ast.Name) \
                    and stmt.value.id == "H" \
                    and isinstance(stmt.slice, ast.Constant):
                r = stmt.slice.value
                if not (0 <= r < l.R):
                    findings.append(Finding(
                        CODEGEN_PASS, ERROR, subject,
                        f"combine_h() indexes H[{r}] outside rank 0..{l.R - 1}"))
        got_h: dict[tuple[int, int], dict] = {}
        for stmt in node.body:
            if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                    and isinstance(stmt.targets[0], ast.Name) \
                    and stmt.targets[0].id.startswith("c_"):
                _, i, j = stmt.targets[0].id.split("_")
                expr = stmt.value
                # strip the trailing (...).astype(out_dtype) call
                if isinstance(expr, ast.Call) \
                        and isinstance(expr.func, ast.Attribute):
                    expr = expr.func.value
                terms = {}
                try:
                    _coeff_from_expr(expr, terms)
                except ValueError as e:
                    findings.append(Finding(
                        CODEGEN_PASS, ERROR, subject,
                        f"combine_h() c_{i}_{j}: unparseable combine ({e})"))
                    continue
                got_h[(int(i), int(j))] = {k: v for k, v in terms.items()
                                           if v != 0}
        for i in range(l.m):
            for j in range(l.n):
                want = {f"H[{r}]": int(l.W[r, i, j]) for r in range(l.R)
                        if l.W[r, i, j] != 0}
                if got_h.get((i, j), {}) != want:
                    findings.append(Finding(
                        CODEGEN_PASS, ERROR, subject,
                        f"combine_h() C[{i},{j}]: emitted {got_h.get((i, j))} "
                        f"!= scheme W column {want}"))
    return findings
