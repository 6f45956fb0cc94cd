"""Operations and bytes of a conv network's conv stages, from the
configuration: LeNet-5's real work, ``k*k*C_in x C_out`` per output
position, never the padded 400 x 100 core the kernel is handed.

A conv stage's core is stepped over every position of its map, and the
program folds the positions into the kernel's rows: at a batch of ``m``
a stage's kernels take ``m * P`` rows, which is how `conv_kernels` tells
them apart from the dense stages' in a trace.  Operands are float32, as
the configuration states.

Per conv layer at ``m`` rows a call, ``K = k*k*C_in`` and ``N = C_out``:

  * forward: ``2 m P K N`` operations; the patches (``m P K``), both
    conductances (``K N`` each) read and the dot products (``m P N``)
    written;
  * training: the outer product ``2 m P K N`` and, above the first
    layer, the error handed down ``2 m P K N``; both conductances read
    and written, the patches and local errors read, and above the first
    layer the patch errors written.  The first layer hands no error down
    (the reference forms none below it), so its kernel's error output is
    no real work.

Bytes count each operand and result of a kernel only in the share of its
executions in which its layout lies in HBM, as `bench/work_pretrain.py`
counts them (`work_pretrain.in_hbm`).
"""
from __future__ import annotations

from bench import trace as tr
from bench import work
from bench import work_pretrain

TILE_M = 128      # the kernels' row block (kernels/crossbar.TILE_M)


def layers(cfg: dict) -> list[tuple[int, int, int]]:
    """Each conv layer's (positions P, patch lanes K, maps N)."""
    out = []
    for c in cfg["conv"]:
        (H, W, C), k = c["in"], int(c["k"])
        out.append(((H - k + 1) * (W - k + 1), k * k * C, int(c["out"])))
    return out


def kernel_rows(m: int, positions: int) -> int:
    """Rows a conv stage's kernel takes at ``m`` rows a call, padded to
    the row block as the kernel's operand carries them."""
    rows = m * positions
    return rows if rows <= TILE_M else -(-rows // TILE_M) * TILE_M


def _result_shapes(op_name: str) -> list[tuple[int, ...]]:
    """The float32 result shapes of an operation's HLO text, a leading
    axis of 1 (one core, as the stacked kernels vmap it) left out."""
    head = op_name.partition("custom-call(")[0]
    out = []
    for m in tr.SHAPE.findall(head.split(" = ", 1)[-1]):
        dims = tuple(int(v) for v in m.split(",")) if m else ()
        out.append(dims[1:] if len(dims) == 3 and dims[0] == 1 else dims)
    return out


def conv_kind(op_name: str, rows: set[int]) -> str | None:
    """Which conv stage kernel an ``XLA Ops`` event is, from its results:
    ``fwd`` (the dot products, (rows, N)) or ``train`` (y (rows, N), dx
    (rows, K), g+' and g-' (K, N)) at one of a conv stage's folded
    ``rows``; None for any other operation.  A one-core kernel's operands
    may lose their core axis in the compile (the stacked kernels'
    ``vmap`` over one core), so the dense stages' classifier
    (`trace.kernel_kind`) is not used."""
    if "custom-call(" not in op_name or "tpu_custom_call" not in op_name:
        return None
    res = _result_shapes(op_name)
    if any(len(r) != 2 for r in res):
        return None
    if len(res) == 1 and res[0][0] in rows:
        return "fwd"
    if len(res) == 4 and res[0][0] in rows and res[1][0] == res[0][0] \
            and res[2] == res[3] == (res[1][1], res[0][1]):
        return "train"
    return None


def conv_kernels(devices, kind: str, cfg: dict, m: int) -> list:
    """The ``kind`` kernels of the conv stages in a reduced trace."""
    rows = {kernel_rows(m, P) for P, _, _ in layers(cfg)}
    return [o for d in devices for o in d.ops if conv_kind(o.name, rows)
            == kind]


def hbm_shares(ops, kind: str) -> list[float] | None:
    """`work_pretrain.hbm_shares` over the given ``kind`` kernel
    executions: for each operand and result, the share of them in which
    it lies in HBM.  None where none ran, or where the HLO text does not
    list each operand and result (`work_pretrain.PARTS`) with its
    layout."""
    flags = [work_pretrain.in_hbm(o.name) for o in ops]
    if not flags or any(len(f) != len(work_pretrain.PARTS[kind])
                        for f in flags):
        return None
    return [sum(col) / len(flags) for col in zip(*flags)]


def _bytes(parts, sizes, shares) -> float:
    shares = [1.0] * len(parts) if shares is None else shares
    return work.F32_BYTES * sum(sizes[p] * s for p, s in zip(parts, shares))


def fwd_work(cfg: dict, m: float, shares=None) -> work.Work:
    """The conv stages' forward work at ``m`` rows a call (module doc)."""
    flops = byts = 0.0
    for P, K, N in layers(cfg):
        sizes = {"x": m * P * K, "g": K * N, "d": m * P * N, None: 0.0}
        flops += 2.0 * m * P * K * N
        byts += _bytes(work_pretrain.PARTS["fwd"], sizes, shares)
    return work.Work(flops, byts)


def train_work(cfg: dict, m: float, shares=None) -> work.Work:
    """The conv stages' training work at ``m`` rows a call (module
    doc)."""
    flops = byts = 0.0
    for i, (P, K, N) in enumerate(layers(cfg)):
        sizes = {"x": m * P * K, "g": K * N, "d": m * P * N, None: 0.0}
        parts = list(work_pretrain.PARTS["train"])
        if i == 0:
            parts[5] = None          # the first layer's error output
        flops += (2.0 if i == 0 else 4.0) * m * P * K * N
        byts += _bytes(parts, sizes, shares)
    return work.Work(flops, byts)


def passes(cfg: dict) -> float:
    """Network operations of a training step per sample over those of a
    recognition pass of the dense chain ``dims`` (`work.network_ops`):
    forward, backward and update on every layer, the conv layers'
    ``P K N`` multiply-adds included; for LeNet-5 3 * 416,520 / 58,920 =
    21.208."""
    dims = cfg["dims"]
    dense = sum(a * b for a, b in zip(dims, dims[1:]))
    return 3.0 * (dense + sum(P * K * N for P, K, N in layers(cfg))) / dense
