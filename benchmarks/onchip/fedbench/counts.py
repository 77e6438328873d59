"""Work counted from shapes: SA-Net's convolution FLOPs and the fold's bytes.

Only convolutions are counted: 2·Cin·Cout for every kernel tap that
lands inside the volume ('SAME' padding taps multiply zeros and are not
work).  The squeeze-and-excitation products, norms and activations are
left out, so the count sits a little under what XLA's cost analysis
reports for the same forward pass.  A training step needs the forward,
the gradient with respect to the weights and, everywhere but the stem
(whose input is data), the gradient with respect to the input: three
times the forward, less the stem's input gradient.  Recomputed
operations never count.
"""
from __future__ import annotations

from typing import List, Tuple

F32 = 4


def _filters(cfg: dict, level: int) -> int:
    return cfg["base_filters"] * 2 ** level


def _taps_1d(n: int, k: int, stride: int) -> int:
    """Kernel taps inside an ``n``-long axis, summed over the outputs of a
    'SAME'-padded convolution."""
    m = -(-n // stride)
    lo = max((m - 1) * stride + k - n, 0) // 2
    return sum(1 for o in range(m) for j in range(k)
               if 0 <= o * stride - lo + j < n)


def _sizes(cfg: dict, level: int) -> List[int]:
    size = list(cfg["volume"])
    for _ in range(level):
        size = [-(-n // 2) for n in size]
    return size


def conv_layers(cfg: dict) -> List[Tuple[str, int, int, int]]:
    """``(name, cin, cout, taps)`` of every convolution in one forward
    pass of one case; ``taps`` counts output voxels times the kernel taps
    inside the volume."""
    levels = cfg["num_levels"]

    def taps(level, k, stride=1):
        n = 1
        for size in _sizes(cfg, level):
            n *= _taps_1d(size, k, stride)
        return n

    def vox(level):
        return taps(level, 1)

    out = [("stem", cfg["in_channels"], _filters(cfg, 0), taps(0, 3))]

    def block(name, cin, cout, level):
        out.append((f"{name}.conv1", cin, cout, taps(level, 3)))
        out.append((f"{name}.conv2", cout, cout, taps(level, 3)))
        if cin != cout:
            out.append((f"{name}.proj", cin, cout, vox(level)))

    for lvl in range(levels):
        c = _filters(cfg, lvl)
        block(f"enc{lvl}.b1", c, c, lvl)
        block(f"enc{lvl}.b2", c, c, lvl)
        if lvl < levels - 1:
            out.append((f"enc{lvl}.down", c, _filters(cfg, lvl + 1),
                        taps(lvl, 3, stride=2)))
    for lvl in range(levels - 2, -1, -1):
        c = _filters(cfg, lvl)
        out.append((f"dec{lvl}.up", _filters(cfg, lvl + 1), c, vox(lvl)))
        for i in range(levels):
            out.append((f"dec{lvl}.attn{i}", _filters(cfg, i), c, vox(lvl)))
        block(f"dec{lvl}.block", c, c, lvl)
        out.append((f"dec{lvl}.head", c, cfg["out_channels"], vox(lvl)))
    return out


def forward_flops(cfg: dict) -> int:
    """Convolution FLOPs of one forward pass of one case."""
    return sum(2 * cin * cout * t for _, cin, cout, t in conv_layers(cfg))


def train_flops_per_case(cfg: dict) -> int:
    """Forward + weight gradient + input gradient, stem input excluded."""
    _, cin, cout, t = conv_layers(cfg)[0]
    return 3 * forward_flops(cfg) - 2 * cin * cout * t


def round_flops(cfg: dict, traffic: dict) -> int:
    """Model FLOPs of one federated round: every site's local steps."""
    return (train_flops_per_case(cfg) * traffic["batch"]
            * traffic["local_steps"] * traffic["sites"])


def fold_bytes(num_params: int, sites: int) -> int:
    """Least HBM traffic of one Eq. 1 fold over ``[S, N]`` fp32: every
    row read once, the ``[S]`` weights read, the ``[N]`` global written.
    N is the model's own size, so padding counts against the kernel."""
    return F32 * (sites * num_params + sites + num_params)


def num_params(cfg: dict) -> int:
    """Parameter count of SA-Net at the configuration's widths."""
    levels, ratio = cfg["num_levels"], cfg["se_ratio"]
    n = 0

    def conv(k, cin, cout):
        return k ** 3 * cin * cout + cout

    def se(c):
        hidden = max(c // ratio, 4)
        return 2 * c * hidden

    def block(cin, cout):
        return (2 * cin + conv(3, cin, cout) + 2 * cout + conv(3, cout, cout)
                + se(cout) + (conv(1, cin, cout) if cin != cout else 0))

    n += conv(3, cfg["in_channels"], _filters(cfg, 0))
    for lvl in range(levels):
        c = _filters(cfg, lvl)
        n += 2 * block(c, c)
        if lvl < levels - 1:
            n += conv(3, c, _filters(cfg, lvl + 1))
    for lvl in range(levels - 1):
        c = _filters(cfg, lvl)
        n += sum(conv(1, _filters(cfg, i), c) for i in range(levels))
        n += se(c * levels)
    for lvl in range(levels - 2, -1, -1):
        c = _filters(cfg, lvl)
        n += conv(1, _filters(cfg, lvl + 1), c) + block(c, c)
        n += conv(1, c, cfg["out_channels"])
    return n
