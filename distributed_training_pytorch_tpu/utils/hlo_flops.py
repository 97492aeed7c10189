"""Matmul/conv FLOP accounting straight from optimized HLO text.

Walks a compiled executable's ``as_text()`` for every ``convolution`` and
``dot`` instruction (fused bodies included — each ``%name`` defines once) and
computes the FLOPs XLA's own cost model attributes to it: ``2 * out_elems *
reduction_size``, reduction = rhs spatial x input-feature (convs, from
``dim_labels`` — the HLO rhs kernel already carries C_in/groups for grouped
convs, so NO further feature_group_count division; regression-tested) or the
contracting-dims product (dots). The sum is the program's *executed* MXU FLOPs — what the
compiler kept after folding, as opposed to the layer-formula *nominal* count
an eager executor (the torch reference) performs.

Born from the r4 VGG16 itemization (``scripts/itemize_flops.py``): the
long-suspected "XLA undercounts conv backward" gap turned out to be the
compiler legitimately strength-reducing the 32x32 config's degenerate
classifier (a 1x1 feature map replicated to 7x7 by adaptive pool folds from
a 25088-wide to an effective 512-wide GEMM). fwd/dgrad/wgrad conv FLOPs
reconcile per-instruction.
"""

from __future__ import annotations

import re

__all__ = [
    "itemize_hlo_matmul_flops",
    "executed_matmul_flops",
    "xla_cost_analysis",
    "bytes_accessed",
    "arithmetic_intensity",
    "aval_bytes",
]


def xla_cost_analysis(compiled) -> dict:
    """``compiled.cost_analysis()`` as a plain dict ({} when the backend
    reports none)."""
    return dict(compiled.cost_analysis() or {})


def bytes_accessed(compiled) -> float | None:
    """XLA's ``bytes accessed`` estimate for a compiled executable: total
    HBM traffic (operand reads + output writes, post-fusion) the cost model
    attributes to the program. The memory-side twin of the ``flops`` entry —
    together they place a program on the roofline. For a ``lax.scan``-chained
    program the body is counted once, matching the FLOP convention. None when
    the backend reports no cost analysis."""
    value = xla_cost_analysis(compiled).get("bytes accessed")
    return float(value) if value is not None else None


def arithmetic_intensity(compiled, *, flops: float | None = None) -> float | None:
    """FLOPs per HBM byte — the roofline x-coordinate. Above the machine's
    peak_FLOPs/peak_bandwidth ridge point a program can be compute-bound;
    below it the bandwidth floor caps MFU no matter the dtype. Mixed
    precision moves BOTH axes (bf16 halves the bytes of every activation/
    weight access and doubles MXU peak), which is why the precision sweep in
    ``docs/performance.md`` reports intensity per dtype.

    ``flops`` overrides the numerator (e.g. the analytic model count);
    default is ``cost_analysis()``'s executed estimate. None when either
    side of the ratio is unavailable or zero."""
    denom = bytes_accessed(compiled)
    if not denom:
        return None
    numer = flops if flops is not None else float(xla_cost_analysis(compiled).get("flops", 0.0))
    if not numer:
        return None
    return numer / denom

DEF_RE = re.compile(r"^(?:ROOT )?%([\w.\-]+) = (\w+)\[([0-9,]*)\]")

# Element sizes for the dtypes HLO shapes name; anything unlisted (tuples,
# opaque tokens) falls back to 4 — per-op bytes are a roofline estimate, not
# an allocator accounting.
DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}
def aval_bytes(shape, dtype) -> float:
    """Byte size of one array leaf at the HLO dtype widths above — the
    sizing the static audit's donation report uses (``analysis.hlo_audit``:
    undonated bytes of param/optimizer-state inputs), so a lint report and a
    per-op roofline row account memory with the same table. ``dtype``
    accepts numpy/jax dtypes or names; anything unmappable (extended dtypes
    like typed PRNG keys) falls back to 4 bytes/element — an estimate, same
    contract as the per-op ``bytes`` rows."""
    import numpy as np

    n = 1
    for dim in shape:
        n *= int(dim)
    try:
        name = np.dtype(dtype).name
    except TypeError:
        return float(n * 4)
    if name == "bool":
        hlo = "pred"
    elif name.startswith("float"):
        hlo = "f" + name[len("float"):]
    elif name.startswith("bfloat"):
        hlo = "bf" + name[len("bfloat"):]
    elif name.startswith("uint"):
        hlo = "u" + name[len("uint"):]
    elif name.startswith("int"):
        hlo = "s" + name[len("int"):]
    elif name.startswith("complex"):
        hlo = "c" + name[len("complex"):]
    else:
        hlo = name
    return float(n * DTYPE_BYTES.get(hlo, 4))


CONV_RE = re.compile(r" convolution\((.*?)\), window={(.*?)}, dim_labels=(\S+?)[,\s]")
DOT_RE = re.compile(r" dot\((.*?)\),.*?lhs_contracting_dims={([0-9,]*)}")
OPERAND_RE = re.compile(r"%([\w.\-]+)")
OPNAME_RE = re.compile(r'op_name="([^"]*)"')


def _dims(s: str) -> list[int]:
    return [int(x) for x in s.split(",") if x] if s else []


def _numel(dims: list[int]) -> int:
    n = 1
    for x in dims:
        n *= x
    return n


def itemize_hlo_matmul_flops(hlo_text: str) -> list[dict]:
    """Per-instruction rows: ``{name, kind, out_elems, reduction, flops,
    bytes, dim_labels, op_name}`` for every conv/dot in the module.

    ``bytes`` is the op's roofline denominator — output write + operand reads
    at the HLO shapes' dtypes (operands with unparsed shapes contribute 0) —
    so ``flops / bytes`` places the instruction on the roofline next to the
    whole-program ``arithmetic_intensity`` figure. Joined into profile
    reports by ``profiling.report.flops_index``."""
    shapes: dict[str, tuple[list[int], str]] = {}
    stripped = [line.strip() for line in hlo_text.splitlines()]
    for line in stripped:
        m = DEF_RE.match(line)
        if m:
            shapes[m.group(1)] = (_dims(m.group(3)), m.group(2))

    def op_bytes(out_dims: list[int], out_dtype: str, operand_names: list[str]) -> float:
        total = _numel(out_dims) * DTYPE_BYTES.get(out_dtype, 4)
        for op in operand_names:
            if op in shapes:
                dims, dtype = shapes[op]
                total += _numel(dims) * DTYPE_BYTES.get(dtype, 4)
        return float(total)

    rows: list[dict] = []
    for line in stripped:
        d = DEF_RE.match(line)
        if not d:
            continue
        name, out_dtype, out = d.group(1), d.group(2), _dims(d.group(3))
        out_elems = _numel(out)
        opname = OPNAME_RE.search(line)
        opname = opname.group(1) if opname else ""
        m = CONV_RE.search(line)
        if m:
            ops = OPERAND_RE.findall(m.group(1))
            rhs = shapes.get(ops[1]) if len(ops) > 1 else None
            if rhs is None:
                continue
            rhs_dims = rhs[0]
            labels = m.group(3)  # e.g. b01f_01io->b01f
            rhs_spec = labels.split("_")[1].split("-")[0]
            # Reduction per output element = rhs spatial dims x rhs input
            # feature ('i'); 'o' is the output-feature dim, not reduced.
            red = 1
            for pos, ch in enumerate(rhs_spec):
                if ch.isdigit() or ch == "i":
                    red *= rhs_dims[pos]
            # Grouped convs need NO division here: the HLO rhs kernel's
            # input-feature dim is already C_in/groups (verified on a
            # groups=8 3x3 conv: rhs 'i' dim = 1).
            rows.append(dict(name=name, kind="conv", out_elems=out_elems,
                             reduction=red, flops=2.0 * out_elems * red,
                             bytes=op_bytes(out, out_dtype, ops[:2]),
                             dim_labels=labels, op_name=opname))
            continue
        m = DOT_RE.search(line)
        if m:
            ops = OPERAND_RE.findall(m.group(1))
            lhs = shapes.get(ops[0]) if ops else None
            if lhs is None:
                continue
            red = 1
            for dim in _dims(m.group(2)):
                red *= lhs[0][dim]
            rows.append(dict(name=name, kind="dot", out_elems=out_elems,
                             reduction=red, flops=2.0 * out_elems * red,
                             bytes=op_bytes(out, out_dtype, ops[:2]),
                             dim_labels="", op_name=opname))
    return rows


def executed_matmul_flops(compiled) -> float | None:
    """Executed MXU FLOPs of a jax compiled executable (sum over conv/dot
    instructions of its optimized HLO). For a ``lax.scan``-chained program
    this counts the body once, matching ``cost_analysis()``'s convention.

    Returns None when the counting convention does not apply: XLA:TPU lowers
    transformer ``dot_general``s to *windowed* convolutions (e.g.
    ``window={size=3x1x12 pad=2_2x0_0x11_11 rhs_reversal=...}``) whose
    window taps are mostly padding — the kernel-spatial formula then counts
    phantom work (measured 6.7x cost_analysis on ViT-B). The guard: accept
    the sum only when it reconciles with ``cost_analysis()`` (which also
    counts VPU elementwise, so a valid matmul-only sum lands below it).

    A parser regression is NOT silent (ADVICE r4): the documented
    windowed-conv mismatch only ever OVER-counts (phantom padding taps), so
    the silent None is reserved for ratios above the band; zero matches, or a
    ratio below it (an undercount — e.g. one of the two regexes breaking
    while the other still matches), warns loudly.

    Custom calls (Pallas kernels) are opaque to both this walk and to
    ``cost_analysis()`` — a flash-attention program's counted FLOPs exclude
    the attention matmuls entirely; comparisons against nominal counts must add the
    kernel's analytic FLOPs back."""
    total = sum(r["flops"] for r in itemize_hlo_matmul_flops(compiled.as_text()))
    cost = xla_cost_analysis(compiled)
    xla = float(cost.get("flops", 0.0))
    if total == 0.0 and xla > 1e9:
        import warnings

        warnings.warn(
            "executed_matmul_flops: no convolution/dot instructions matched in "
            f"an HLO module whose cost_analysis reports {xla:.2e} flops — the "
            "HLO text format likely changed and the parser needs updating "
            "(this is a parser regression, not the windowed-conv convention "
            "mismatch)."
        )
        return None
    if xla > 0:
        if total == 0.0:
            return None  # matmul-free (or trivial) program; warned above if big
        if total / xla < 0.3:
            import warnings

            warnings.warn(
                f"executed_matmul_flops: matched conv/dot sum {total:.2e} is "
                f"below 0.3x cost_analysis ({xla:.2e}) — an UNDER-count, which "
                "the windowed-conv convention mismatch cannot produce; likely "
                "a partial HLO-parser regression (one instruction form no "
                "longer matching)."
            )
            return None
        if total / xla > 1.1:
            return None  # documented windowed-conv overcount (see docstring)
    return total
