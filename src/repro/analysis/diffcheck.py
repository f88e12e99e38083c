"""quantcheck layer 2: cross-backend differential kernel verification (QL304).

Sweeps every kernel-table layout over a generated shape lattice — odd and
edge-case K, grid-non-divisible dims, single- and multi-K-tile — and checks
the Pallas kernels (interpret mode: bit-identical kernel semantics without
a TPU) against the pure-jnp refs (``kernels/ref.py``) through the real
dispatcher ``kernels.ops.qtensor_matmul``. Both runs are recorded (reusing
the QL207 coverage recorders plus a Pallas-side wrapper), so each parity
row also *proves* which kernel served the layout — dispatch drift shows up
as a QL304 error, not a silently-green comparison of the wrong kernel.

Exactness policy (empirical and by construction):
  - single-tile float shapes (M <= 128, N <= 128, K <= block_k = 512): both
    paths run one dot_general of *identical shape* -> bit-exact, asserted;
  - the W8A8 integer path: int32 accumulation is associative -> bit-exact
    at any shape, tiled or not;
  - everything else runs under a relative tolerance: a multi-K-tile grid
    re-associates the contraction sum, and even a multi-N-tile grid changes
    the gemm shape XLA's CPU backend sees, which re-vectorizes the
    reduction (observed: one-ulp differences at N = 129, single K step).
    A bit-exact assert there would be asserting float addition is
    associative.

The full lattice (>= 20 shapes per layout) runs in the analysis-verify CI
job; the default lint run sweeps a 3-shape smoke subset per layout.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.coverage import FALLBACK, _record_kernels
from repro.analysis.report import Report
from repro.analysis.trace import MATMUL_LAYOUTS, _a_state_for, _export_qt
from repro.kernels.envelope import check_envelope

_BLOCK_K = 512      # default K tile of every matmul kernel
#: relative error bound for multi-K-tile float paths (empirically ~1e-6 on
#: CPU interpret vs ref; 8x headroom so CI noise never flakes the lint)
_REL_TOL = 8e-6

#: layout -> (ref kernel, pallas kernel) the dispatcher must pick
EXPECTED_KERNELS: Dict[str, Tuple[str, str]] = {
    "w4_packed": ("dequant_matmul_w4_ref", "dequant_matmul_w4"),
    "w4a8_packed": ("dequant_matmul_w4_ref", "dequant_matmul_w4"),
    "w8a8": ("qmatmul_int8_ref", "qmatmul_int8"),
    "w8_weight_only": ("dequant_matmul_w8_ref", "dequant_matmul_w8"),
    "w4_odd_unpacked": ("dequant_matmul_w8_ref", "dequant_matmul_w8"),
    "experts_batched": ("dequant_matmul_batched_ref", "dequant_matmul_batched"),
}

_PALLAS_KERNELS = ("dequant_matmul_w4", "dequant_matmul_w8",
                   "dequant_matmul_batched", "qmatmul_int8")


@dataclasses.dataclass(frozen=True)
class ParityRow:
    """One (layout, shape) cell of the QL304 parity matrix."""
    layout: str
    shape: Tuple[int, int, int, int]   # (e, m, k, n); e = 1 for 2-D layouts
    kernel_ref: str
    kernel_pallas: str
    mode: str                          # "bit-exact" | "tolerance"
    k_steps: int
    max_abs_err: float
    bound: float                       # 0.0 in bit-exact mode
    ok: bool

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


# ------------------------------------------------------------ shape lattice
def shape_lattice(layout: str) -> List[Tuple[int, int, int, int]]:
    """(e, m, k, n) sweep for one layout: edge K (1-2 rows/cols), odd K,
    non-block-divisible everything, plus multi-K-tile rows. Every shape is
    inside the layout's envelope (asserted)."""
    ms = (1, 5, 33)
    ns = (8, 24, 120, 129)
    if layout in ("w4_packed", "w4a8_packed"):
        ks = (2, 6, 16, 62, 64, 126, 254, 256, 510, 512, 514, 1026)
    elif layout == "w4_odd_unpacked":
        ks = (3, 5, 33, 63, 127, 255, 333, 511, 513, 1025)
    elif layout in ("w8a8", "w8_weight_only"):
        ks = (1, 7, 24, 48, 127, 128, 255, 384, 512, 640, 1024, 1100)
    elif layout == "experts_batched":
        ks = (4, 6, 16, 62, 64, 126, 128, 254, 256, 512)
    else:
        raise KeyError(layout)
    es = (1, 2, 3, 5) if layout == "experts_batched" else (1,)
    shapes: List[Tuple[int, int, int, int]] = []
    for rep in range(2):   # two passes with shifted m/n pairing -> >= 20 rows
        for i, k in enumerate(ks):
            e = es[(i + rep) % len(es)]
            m = ms[(i + rep) % len(ms)]
            n = ns[(i + 2 * rep) % len(ns)]
            if (e, m, k, n) in shapes:
                n = ns[(i + 2 * rep + 1) % len(ns)]
            shapes.append((e, m, k, n))
    for e, m, k, n in shapes:
        check_envelope(layout, m, k, n, e)
    return shapes


def _layout_row(layout: str):
    for name, _, bits, batch_dims, with_a in MATMUL_LAYOUTS:
        if name == layout:
            return bits, batch_dims, with_a
    raise KeyError(layout)


def _example_at(layout: str, e: int, m: int, k: int, n: int):
    bits, batch_dims, with_a = _layout_row(layout)
    if batch_dims == 1:
        qt = _export_qt((e, k, n), bits, batch_dims=1)
        x = jax.random.normal(jax.random.key(13), (e, m, k), jnp.float32)
    else:
        qt = _export_qt((k, n), bits, batch_dims=0)
        x = jax.random.normal(jax.random.key(13), (m, k), jnp.float32)
    return x, qt, (_a_state_for(x) if with_a else None)


@contextlib.contextmanager
def record_pallas(hits: List[str]):
    """Record which Pallas kernel ``ops`` dispatches (the interpret-mode
    run); mirrors coverage's ref-side recorder."""
    import repro.kernels.ops as kops
    saved = []
    for fname in _PALLAS_KERNELS:
        orig = getattr(kops, fname)

        def rec_fn(*a, _orig=orig, _label=fname, **kw):
            hits.append(_label)
            return _orig(*a, **kw)

        saved.append((fname, orig))
        setattr(kops, fname, rec_fn)
    try:
        yield
    finally:
        for fname, orig in saved:
            setattr(kops, fname, orig)


def _first_kernel(hits: List[str]) -> str:
    kernels = [h for h in hits if h != FALLBACK]
    return kernels[0] if kernels else (FALLBACK if hits else "none")


# ------------------------------------------------------------------ checks
def check_parity(layout: str, e: int, m: int, k: int, n: int) -> ParityRow:
    """Run one lattice cell through both backends and compare."""
    from repro.kernels import ops as kops

    x, qt, a_state = _example_at(layout, e, m, k, n)
    ref_hits: List[str] = []
    with _record_kernels(ref_hits):
        ref_out = jax.block_until_ready(kops.qtensor_matmul(
            x, qt, a_state=a_state, backend="xla"))
    pl_hits: List[str] = []
    with record_pallas(pl_hits):
        pl_out = jax.block_until_ready(kops.qtensor_matmul(
            x, qt, a_state=a_state, backend="pallas", interpret=True))

    k_steps = -(-k // min(_BLOCK_K, k))
    integer_path = layout == "w8a8"
    single_tile = m <= 128 and n <= 128 and k <= _BLOCK_K
    bit_exact = integer_path or single_tile
    ref_np = np.asarray(ref_out, np.float32)
    pl_np = np.asarray(pl_out, np.float32)
    err = float(np.max(np.abs(ref_np - pl_np))) if ref_np.size else 0.0
    if bit_exact:
        bound = 0.0
        ok = bool(np.array_equal(ref_np, pl_np))
    else:
        bound = _REL_TOL * max(1.0, float(np.max(np.abs(ref_np))))
        ok = err <= bound
    return ParityRow(
        layout=layout, shape=(e, m, k, n),
        kernel_ref=_first_kernel(ref_hits),
        kernel_pallas=_first_kernel(pl_hits),
        mode="bit-exact" if bit_exact else "tolerance",
        k_steps=k_steps, max_abs_err=err, bound=bound, ok=ok)


def run_diffcheck(layouts: Optional[Tuple[str, ...]] = None, *,
                  smoke: bool = False) -> Tuple[Report, List[ParityRow]]:
    """Differential sweep; ``smoke=True`` trims the lattice to 3 shapes per
    layout (the default lint run; CI's analysis-verify job runs the full
    lattice)."""
    rep = Report()
    rows: List[ParityRow] = []
    names = layouts or tuple(r[0] for r in MATMUL_LAYOUTS)
    for layout in names:
        lattice = shape_lattice(layout)
        if smoke:
            # one edge-K, one odd/middle, one grid-non-divisible
            lattice = lattice[:3]
        exp_ref, exp_pl = EXPECTED_KERNELS[layout]
        for e, m, k, n in lattice:
            row = check_parity(layout, e, m, k, n)
            rows.append(row)
            where = f"diff:{layout}#e{e}m{m}k{k}n{n}"
            if row.kernel_ref != exp_ref or row.kernel_pallas != exp_pl:
                rep.add("QL304", "dispatch-drift", "error", where,
                        f"layout dispatched to ({row.kernel_ref}, "
                        f"{row.kernel_pallas}); the kernel table promises "
                        f"({exp_ref}, {exp_pl}) — the parity result proves "
                        "the wrong kernel")
            elif not row.ok:
                detail = ("bit-exactness" if row.mode == "bit-exact" else
                          f"tolerance {row.bound:.3g}")
                rep.add("QL304", "kernel-parity", "error", where,
                        f"Pallas-interpret vs XLA ref differ by "
                        f"{row.max_abs_err:.3g} (mode {row.mode}, "
                        f"k_steps={row.k_steps}) — {detail} violated; the "
                        "kernel and its ref have diverged")
    return rep, rows


def parity_table(rows: List[ParityRow]) -> str:
    head = (f"{'layout':18s} {'(e,m,k,n)':>18s} {'mode':>10s} "
            f"{'kst':>3s} {'max|err|':>10s} {'bound':>9s}  kernel")
    lines = [head, "-" * len(head)]
    for r in rows:
        mark = "" if r.ok else "  <- FAIL"
        lines.append(
            f"{r.layout:18s} {str(r.shape):>18s} {r.mode:>10s} "
            f"{r.k_steps:>3d} {r.max_abs_err:>10.3g} {r.bound:>9.3g}  "
            f"{r.kernel_pallas}{mark}")
    return "\n".join(lines)


def parity_json(rows: List[ParityRow]) -> dict:
    return {
        "rows": [r.to_json() for r in rows],
        "layouts": sorted({r.layout for r in rows}),
        "n_rows": len(rows),
        "n_fail": sum(1 for r in rows if not r.ok),
    }
