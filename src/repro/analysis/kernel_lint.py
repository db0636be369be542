"""Static lint of the Pallas kernels in ``repro.kernels``.

Each kernel wrapper in ``repro.kernels`` encodes its grid/BlockSpec contract
imperatively (asserts, ``jnp.pad`` calls).  This pass re-states those
contracts declaratively as :class:`KernelModel` records — the wrapper's
padded operand dims, block shapes and index maps for a representative
problem size — and checks them with plain integer arithmetic:

  * **K001** blocking: every block shape must divide its (post-padding)
    operand dims; a dimension the wrapper pads explicitly is an info note
    (wasted tiles), a dimension the wrapper *asserts* on is an error at the
    offending problem size.
  * **K002** index-map bounds: index maps return **block** indices (the
    old-style BlockSpec convention all these kernels use); over every grid
    corner the mapped block must satisfy ``0 <= b`` and
    ``(b+1)*block <= dim``.  Affine/monotone maps make corners sufficient.
  * **K003** output aliasing: a grid dimension the output index map ignores
    means the same output block is revisited across that dimension's steps.
    On TPU the grid runs sequentially with the *last* dim innermost, so a
    revisit is only sound as the declared accumulation pattern over a
    trailing contiguous suffix of grid dims (matmul's K loop, flash's KV
    loop); anything else is a read-modify-write hazard.

``lint_kernels()`` checks every built-in kernel at representative sizes;
``check_model`` is the generic engine the tests drive with deliberately
broken models.
"""
from __future__ import annotations

from dataclasses import dataclass
from itertools import product
from typing import Callable, List, Optional, Sequence, Tuple

from repro.analysis.findings import ERROR, INFO, Finding


@dataclass
class OperandSpec:
    """One pallas_call operand as the wrapper builds it."""
    name: str
    dims: Tuple[int, ...]            # operand dims after wrapper padding
    block: Tuple[int, ...]           # BlockSpec block_shape
    index_map: Callable              # grid point -> block-index tuple
    padded_dims: Tuple[int, ...] = ()  # dims the wrapper jnp.pad-ed


@dataclass
class KernelModel:
    """Declarative contract of one kernel at one problem size."""
    name: str
    grid: Tuple[int, ...]
    inputs: List[OperandSpec]
    output: OperandSpec
    # grid dims whose output-block revisits are the by-design accumulation
    # (carried in VMEM scratch across the sequential innermost steps)
    accum_dims: Tuple[int, ...] = ()
    size_tag: str = ""               # representative-size label for messages


def _corner_points(grid: Tuple[int, ...]):
    return product(*[(0,) if g == 1 else (0, g - 1) for g in grid])


def _map_at(spec: OperandSpec, point) -> Tuple[int, ...]:
    # tdfir's left-edge clamp uses jnp.maximum: coerce array entries to int
    return tuple(int(b) for b in spec.index_map(*point))


def check_model(model: KernelModel) -> List[Finding]:
    """Generic K001/K002/K003 checks over one KernelModel."""
    out: List[Finding] = []
    subject = model.name
    tag = f" [{model.size_tag}]" if model.size_tag else ""

    def add(rule_id, severity, message, **ctx):
        out.append(Finding(rule_id, severity, message + tag,
                           plan_field=None, subject=subject, context=ctx))

    operands = model.inputs + [model.output]
    for spec in operands:
        if len(spec.dims) != len(spec.block):
            add("K001", ERROR,
                f"{spec.name}: block rank {len(spec.block)} != operand "
                f"rank {len(spec.dims)}")
            continue
        for d, (dim, blk) in enumerate(zip(spec.dims, spec.block)):
            if blk <= 0 or dim <= 0:
                add("K001", ERROR,
                    f"{spec.name}: nonpositive dim/block {dim}/{blk} "
                    f"at axis {d}")
            elif dim % blk != 0:
                # the wrapper either padded this dim (then dims here are
                # post-padding and divide) or never guaranteed divisibility
                add("K001", ERROR,
                    f"{spec.name}: dim {dim} % block {blk} != 0 at axis "
                    f"{d} and the wrapper neither pads nor asserts it")
            elif d in spec.padded_dims:
                add("K001", INFO,
                    f"{spec.name}: axis {d} is explicitly padded to "
                    f"{dim} (block {blk}) — divisible by construction, "
                    "padding tiles compute garbage that is sliced off")

    # K002: block-index bounds over the grid corners
    for spec in operands:
        if len(spec.dims) != len(spec.block):
            continue
        for point in _corner_points(model.grid):
            try:
                bidx = _map_at(spec, point)
            except Exception as e:
                add("K002", ERROR,
                    f"{spec.name}: index_map raised at grid point "
                    f"{point}: {e!r}")
                break
            if len(bidx) != len(spec.dims):
                add("K002", ERROR,
                    f"{spec.name}: index_map returns rank {len(bidx)} "
                    f"for a rank-{len(spec.dims)} operand")
                break
            oob = [d for d, (b, dim, blk)
                   in enumerate(zip(bidx, spec.dims, spec.block))
                   if b < 0 or (b + 1) * blk > dim]
            if oob:
                add("K002", ERROR,
                    f"{spec.name}: block index {bidx} at grid point "
                    f"{point} is out of bounds on axes {oob} "
                    f"(dims {spec.dims}, block {spec.block})")
                break

    # K003: output revisits across grid steps
    if len(model.output.dims) == len(model.output.block):
        base = tuple(0 for _ in model.grid)
        try:
            base_idx = _map_at(model.output, base)
            insensitive = []
            for d, g in enumerate(model.grid):
                if g <= 1:
                    continue          # a single step cannot revisit
                probe = list(base)
                probe[d] = 1
                if _map_at(model.output, tuple(probe)) == base_idx:
                    insensitive.append(d)
        except Exception:
            insensitive = []          # K002 already reported the map error
        if insensitive:
            n = len(model.grid)
            trailing = list(range(n - len(insensitive), n))
            if insensitive != trailing:
                add("K003", ERROR,
                    f"output block is revisited across non-innermost grid "
                    f"dims {insensitive} (grid {model.grid}): the "
                    "sequential-accumulation pattern only holds for a "
                    "trailing suffix")
            else:
                undeclared = [d for d in insensitive
                              if d not in model.accum_dims]
                if undeclared:
                    add("K003", ERROR,
                        f"output block is revisited across grid dims "
                        f"{undeclared} but the kernel declares no "
                        "accumulation over them — read-modify-write "
                        "hazard between grid steps")
                else:
                    add("K003", INFO,
                        f"output accumulates over trailing grid dims "
                        f"{insensitive} (declared reduction, VMEM-carried)")
    return out


# ---------------------------------------------------------------------------
# Built-in kernel models: each builder replicates its wrapper's padding /
# assert logic for a problem size, reporting wrapper asserts as K001 errors.
# ---------------------------------------------------------------------------

def matmul_model(m: int = 300, n: int = 200, k: int = 150, *,
                 block_m: int = 128, block_n: int = 128, block_k: int = 128
                 ) -> Tuple[Optional[KernelModel], List[Finding]]:
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    pm, pn, pk = (-m) % bm, (-n) % bn, (-k) % bk
    mp, np_, kp = m + pm, n + pn, k + pk
    model = KernelModel(
        name="matmul", grid=(mp // bm, np_ // bn, kp // bk),
        inputs=[
            OperandSpec("a", (mp, kp), (bm, bk),
                        lambda i, j, kk: (i, kk),
                        padded_dims=(0,) * (pm > 0) + (1,) * (pk > 0)),
            OperandSpec("b", (kp, np_), (bk, bn),
                        lambda i, j, kk: (kk, j),
                        padded_dims=(0,) * (pk > 0) + (1,) * (pn > 0)),
        ],
        output=OperandSpec("o", (mp, np_), (bm, bn),
                           lambda i, j, kk: (i, j)),
        accum_dims=(2,), size_tag=f"{m}x{k}@{k}x{n}")
    return model, []


def flash_attention_model(bh: int = 8, sq: int = 1024, skv: int = 1024,
                          d: int = 64, *, block_q: int = 512,
                          block_kv: int = 512
                          ) -> Tuple[Optional[KernelModel], List[Finding]]:
    bq, bkv = min(block_q, sq), min(block_kv, skv)
    if sq % bq != 0 or skv % bkv != 0:
        return None, [Finding(
            "K001", ERROR,
            f"flash_attention: sq {sq} % block_q {bq} or skv {skv} % "
            f"block_kv {bkv} nonzero — the wrapper asserts (no padding "
            "path)", subject="flash_attention")]
    model = KernelModel(
        name="flash_attention", grid=(bh, sq // bq, skv // bkv),
        inputs=[
            OperandSpec("q", (bh, sq, d), (1, bq, d),
                        lambda b, i, j: (b, i, 0)),
            OperandSpec("k", (bh, skv, d), (1, bkv, d),
                        lambda b, i, j: (b, j, 0)),
            OperandSpec("v", (bh, skv, d), (1, bkv, d),
                        lambda b, i, j: (b, j, 0)),
        ],
        output=OperandSpec("o", (bh, sq, d), (1, bq, d),
                           lambda b, i, j: (b, i, 0)),
        accum_dims=(2,), size_tag=f"bh{bh} sq{sq} skv{skv}")
    return model, []


def decode_attention_model(bh: int = 8, s: int = 2048, d: int = 64, *,
                           block_kv: int = 512
                           ) -> Tuple[Optional[KernelModel], List[Finding]]:
    bkv = min(block_kv, s)
    if s % bkv != 0:
        return None, [Finding(
            "K001", ERROR,
            f"decode_attention: cache seq {s} % block_kv {bkv} != 0 — "
            "the wrapper asserts (no padding path)",
            subject="decode_attention")]
    model = KernelModel(
        name="decode_attention", grid=(bh, s // bkv),
        inputs=[
            OperandSpec("q", (bh, 1, d), (1, 1, d),
                        lambda b, j: (b, 0, 0)),
            OperandSpec("k_cache", (bh, s, d), (1, bkv, d),
                        lambda b, j: (b, j, 0)),
            OperandSpec("v_cache", (bh, s, d), (1, bkv, d),
                        lambda b, j: (b, j, 0)),
        ],
        # the cache length is a whole SMEM scalar, not a blocked operand
        output=OperandSpec("o", (bh, 1, d), (1, 1, d),
                           lambda b, j: (b, 0, 0)),
        accum_dims=(1,), size_tag=f"bh{bh} s{s}")
    return model, []


def tdfir_model(f: int = 4, n: int = 1000, k: int = 16, *,
                block_n: int = 512
                ) -> Tuple[Optional[KernelModel], List[Finding]]:
    bn = min(block_n, n)
    if bn < k:
        return None, [Finding(
            "K001", ERROR,
            f"tdfir: block_n {bn} < taps {k} — the sliding history cannot "
            "cover the filter, the wrapper asserts", subject="tdfir")]
    rows = 8                         # filters per tile (tdfir.ROWS)
    pf, pn = (-f) % rows, (-n) % bn
    fp, np_ = f + pf, n + pn
    padded = (0,) * (pf > 0) + (1,) * (pn > 0)

    def prev_map(i, j):
        return (i, max(j - 1, 0))    # wrapper uses jnp.maximum; same clamp

    model = KernelModel(
        name="tdfir", grid=(fp // rows, np_ // bn),
        inputs=[
            OperandSpec("x_prev", (fp, np_), (rows, bn), prev_map,
                        padded_dims=padded),
            OperandSpec("x_cur", (fp, np_), (rows, bn),
                        lambda i, j: (i, j), padded_dims=padded),
            OperandSpec("h", (fp, bn), (rows, bn),
                        lambda i, j: (i, 0),
                        padded_dims=(0,) * (pf > 0) + (1,) * (k < bn)),
        ],
        output=OperandSpec("y", (fp, np_), (rows, bn),
                           lambda i, j: (i, j)),
        size_tag=f"f{f} n{n} k{k}")
    return model, []


_BUILDERS = (matmul_model, flash_attention_model, decode_attention_model,
             tdfir_model)


def kernel_models(builders: Sequence[Callable] = _BUILDERS
                  ) -> Tuple[List[KernelModel], List[Finding]]:
    models, findings = [], []
    for build in builders:
        model, errs = build()
        findings.extend(errs)
        if model is not None:
            models.append(model)
    return models, findings


def lint_kernels(builders: Sequence[Callable] = _BUILDERS) -> List[Finding]:
    """All K-findings for the built-in kernels at representative sizes."""
    models, findings = kernel_models(builders)
    for model in models:
        findings.extend(check_model(model))
    return findings
