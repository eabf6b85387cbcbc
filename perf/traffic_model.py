"""Static HBM-traffic model of the ResNet-50 train step — the offline half
of the byte census (PERF.md §2).

`exp_breakdown.py` measured (on chip, batch 512): 143.5 GB accessed per
full step vs a ~45 GB naive activation estimate, i.e. ~3x inflation, and
the step is bandwidth-bound (81% of the HBM roofline).  `exp_hlo_dump.py`
attributes from the compiled HLO; THIS tool attributes from first
principles so the two can be cross-checked — and so attribution exists
even when no chip is available.

Model
-----
Enumerate every conv/BN/relu/pool/fc tensor of ResNet-50 v1.5 (NHWC,
bf16 activations, f32 params) and count HBM bytes under explicit,
stated assumptions:

  fwd (train):  conv reads in+w, writes out; BN-train reads the conv
                output twice more (batch-stats reduction pass + the
                normalize pass, which fuses scale/shift/relu and the
                next conv's read cannot — it needs the normalized
                value) and writes the normalized output once.
  bwd:          dx needs w + dy; dw needs saved-in + dy; BN bwd reads
                the saved normalized activation + dy and writes dy';
                per conv: reads 2x dy + saved in + w, writes dx + dw.
  optimizer:    SGD-momentum reads grads+params+momentum, writes
                params+momentum (5 x param bytes, f32).

Each tensor is counted twice: LOGICAL bytes (shape product x dtype) and
PADDED bytes (TPU (8,128) tiling on the two minor dims — the same rule
`exp_hlo_dump._nbytes` applies to real HLO layouts, minor dim to 128
lanes, next-minor to 8 sublanes).  The difference, grouped by feature
width, is the lane-padding attribution: C=3 inputs pad 42.7x, C=64 stem
tensors 2x, C>=128 not at all.

Run: python perf/traffic_model.py [batch]    (default 512)
"""

from __future__ import annotations

import dataclasses
import json
import sys


@dataclasses.dataclass
class T:
    """A tensor with its per-step HBM touch counts."""
    name: str
    shape: tuple[int, ...]      # NHWC activations / HWIO weights
    dtype_bytes: int
    fwd_touches: int            # reads+writes in the forward pass
    bwd_touches: int            # reads+writes in the backward pass
    group: str                  # attribution bucket

    def logical(self) -> int:
        n = 1
        for d in self.shape:
            n *= d
        return n * self.dtype_bytes

    def padded(self) -> int:
        dims = list(self.shape)
        if len(dims) >= 1:
            dims[-1] = -(-dims[-1] // 128) * 128
        if len(dims) >= 2:
            dims[-2] = -(-dims[-2] // 8) * 8
        n = 1
        for d in dims:
            n *= d
        return n * self.dtype_bytes


def _bottleneck(tensors, n, h, w, cin, cmid, cout, stride, name):
    """ResNet v1.5 bottleneck: 1x1 cin->cmid, 3x3 (stride) cmid->cmid,
    1x1 cmid->cout, projection cin->cout (stride) on the first block."""
    ho, wo = h // stride, w // stride
    proj = cin != cout
    convs = [
        (f"{name}.conv1", (1, 1, cin, cmid), (n, h, w, cin), (n, h, w, cmid)),
        (f"{name}.conv2", (3, 3, cmid, cmid), (n, h, w, cmid), (n, ho, wo, cmid)),
        (f"{name}.conv3", (1, 1, cmid, cout), (n, ho, wo, cmid), (n, ho, wo, cout)),
    ]
    if proj:
        convs.append((f"{name}.proj", (1, 1, cin, cout), (n, h, w, cin),
                      (n, ho, wo, cout)))
    for cname, wshape, ishape, oshape in convs:
        _conv_bn(tensors, cname, wshape, ishape, oshape)
    # Residual add: reads both branches, writes the sum (fused with the
    # final relu).  Counted once on the output shape.
    tensors.append(T(f"{name}.add", (n, ho, wo, cout), 2,
                     fwd_touches=3, bwd_touches=2, group=_grp(cout)))
    return ho, wo, cout


def _grp(c: int) -> str:
    if c < 8:
        return "C<8 (42x lane pad)"
    if c < 128:
        return "8<=C<128 (lane pad)"
    return "C>=128 (no pad)"


def _conv_bn(tensors, name, wshape, ishape, oshape):
    cin, cout = wshape[2], wshape[3]
    # conv: fwd reads in (counted on the producer's side as a write; we
    # count each activation's touches on ITS tensor) — bookkeeping: the
    # input read belongs to this conv but the tensor entry for the input
    # was already appended by the producer with its own write; to keep
    # attribution by tensor, touches below are per-tensor totals:
    #   activation out: fwd = conv-write + BN-stats read + BN-normalize
    #                   read + normalized write = 4 touches; the NEXT
    #                   layer's read adds 1 more (added by that layer via
    #                   `extra_read`).  bwd: saved-in read (next conv's
    #                   dw), dy read x2, dx write = handled symmetrically.
    # weights: fwd read + bwd read + dw write (f32).
    tensors.append(T(f"{name}.w", wshape, 4, fwd_touches=1, bwd_touches=2,
                     group="weights"))
    # input activation: one read by this conv (fwd) + one saved-read (bwd
    # dw) + one dx write (bwd).
    tensors.append(T(f"{name}.in_rd", ishape, 2, fwd_touches=1,
                     bwd_touches=2, group=_grp(ishape[-1])))
    # output activation: conv write + BN train chain (stats read +
    # normalize read + normalized write) (fwd); dy read x2 + dy' write (bwd).
    tensors.append(T(f"{name}.out", oshape, 2, fwd_touches=4, bwd_touches=3,
                     group=_grp(oshape[-1])))


def build(n: int):
    tensors: list[T] = []
    # Input + stem (7x7/2, BN, relu, maxpool 3x3/2).
    _conv_bn(tensors, "stem", (7, 7, 3, 64), (n, 224, 224, 3),
             (n, 112, 112, 64))
    # Pool input side: the maxpool reads the full-resolution stem output
    # (fwd) and writes dx at that shape (bwd) — 4x the output-side bytes.
    tensors.append(T("stem.pool_in", (n, 112, 112, 64), 2, fwd_touches=1,
                     bwd_touches=1, group=_grp(64)))
    tensors.append(T("stem.pool", (n, 56, 56, 64), 2, fwd_touches=2,
                     bwd_touches=2, group=_grp(64)))
    h = w = 56
    c = 64
    stages = [(3, 64, 256, 1), (4, 128, 512, 2), (6, 256, 1024, 2),
              (3, 512, 2048, 2)]
    for si, (blocks, cmid, cout, stride) in enumerate(stages):
        for b in range(blocks):
            h, w, c = _bottleneck(tensors, n, h, w, c, cmid, cout,
                                  stride if b == 0 else 1, f"c{si+2}.b{b}")
    # Head: global avgpool + fc (input side counted at the c5 output shape).
    tensors.append(T("head.pool_in", (n, 7, 7, 2048), 2, fwd_touches=1,
                     bwd_touches=1, group=_grp(2048)))
    tensors.append(T("head.pool", (n, 1, 1, 2048), 2, fwd_touches=2,
                     bwd_touches=2, group=_grp(2048)))
    tensors.append(T("head.fc.w", (1, 1, 2048, 1000), 4, fwd_touches=1,
                     bwd_touches=2, group="weights"))
    tensors.append(T("head.logits", (n, 1, 1, 1000), 4, fwd_touches=2,
                     bwd_touches=2, group=_grp(1000)))
    return tensors


PARAM_COUNT = 25_557_032  # torchvision resnet50 reference (incl. BN)


def param_count(tensors) -> int:
    total = 0
    for t in tensors:
        if t.group != "weights":
            continue
        k = 1
        for d in t.shape:
            k *= d
        total += k
        # + BN scale/shift per conv output channel (2 x cout), fc bias.
        if t.name.endswith(".w") and not t.name.startswith("head.fc"):
            total += 2 * t.shape[3]
    total += 1000  # fc bias
    return total


# Variant B ("fusion-aware", calibrated against exp_breakdown.py's measured
# split at batch 512: fwd-train 38.1 GB, bwd ~105.2 GB, full 143.5 GB):
#   fwd: XLA fuses the BN normalize into the consumer's read (the
#        normalized activation never lands in HBM) — conv out is touched
#        only by its write + one batch-stats reduction read;
#   bwd: the expensive side — per conv output: dy read for dx, dy read
#        for dw, saved pre-BN read (recompute normalize for dw's input),
#        BN-backward's dgamma/dbeta reduction reads (pre-BN + dy), and
#        the dx write: 6 touches; input-side saved read + dx write: 2.
VARIANT_B = {".out": (2, 6), ".in_rd": (1, 2), ".add": (2, 2),
             ".pool_in": (1, 1), ".pool": (2, 2), ".w": (1, 2),
             ".logits": (2, 2)}


def _variant_b_touches(t: T) -> tuple[int, int]:
    for suffix, (f, b) in VARIANT_B.items():
        if t.name.endswith(suffix):
            return f, b
    return t.fwd_touches, t.bwd_touches


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 512
    tensors = build(n)

    groups: dict[str, dict[str, float]] = {}
    fwd_l = bwd_l = 0
    bn_chain_l = 0
    for t in tensors:
        g = groups.setdefault(t.group, {"logical": 0, "padded": 0})
        touches = t.fwd_touches + t.bwd_touches
        g["logical"] += touches * t.logical()
        g["padded"] += touches * t.padded()
        fwd_l += t.fwd_touches * t.logical()
        bwd_l += t.bwd_touches * t.logical()
        if t.name.endswith(".out"):
            # The BN-train chain's extra touches beyond the conv write:
            # stats read + normalize read + normalized write.
            bn_chain_l += 3 * t.logical()

    # Optimizer pass: 5x param bytes f32 (grads+params+momentum read,
    # params+momentum write).
    pbytes = PARAM_COUNT * 4
    groups["optimizer (5x params f32)"] = {"logical": 5 * pbytes,
                                           "padded": 5 * pbytes}

    tot_l = sum(g["logical"] for g in groups.values())
    tot_p = sum(g["padded"] for g in groups.values())
    print(f"ResNet-50 v1.5 static traffic model, batch {n} "
          f"(assumptions in module docstring)")
    print(f"{'group':28s} {'logical GB':>11s} {'padded GB':>10s} {'pad x':>6s}")
    for name, g in sorted(groups.items(), key=lambda kv: -kv[1]["padded"]):
        ratio = g["padded"] / g["logical"] if g["logical"] else 0
        print(f"{name:28s} {g['logical']/1e9:11.2f} {g['padded']/1e9:10.2f} "
              f"{ratio:6.2f}")
    print(f"{'TOTAL':28s} {tot_l/1e9:11.2f} {tot_p/1e9:10.2f} "
          f"{tot_p/tot_l:6.2f}")
    print(f"fwd logical {fwd_l/1e9:.2f} GB | bwd logical {bwd_l/1e9:.2f} GB "
          f"| BN-train extra chain {bn_chain_l/1e9:.2f} GB "
          f"(within fwd; the stats+normalize touches)")

    # Variant B: fusion-aware split (see VARIANT_B above).
    bf = bb = 0
    for t in tensors:
        f, b = _variant_b_touches(t)
        bf += f * t.logical()
        bb += b * t.logical()
    pb = groups["optimizer (5x params f32)"]["logical"]
    print(f"variant B (fusion-aware): fwd {bf/1e9:.2f} GB, bwd {bb/1e9:.2f} "
          f"GB, +opt {pb/1e9:.2f} GB, total {(bf+bb+pb)/1e9:.2f} GB "
          f"(measured at 512: fwd-train 38.1, bwd ~105.2, full 143.5)")
    print(json.dumps({"batch": n, "logical_gb": round(tot_l / 1e9, 2),
                      "padded_gb": round(tot_p / 1e9, 2),
                      "fwd_logical_gb": round(fwd_l / 1e9, 2),
                      "bwd_logical_gb": round(bwd_l / 1e9, 2),
                      "bn_chain_gb": round(bn_chain_l / 1e9, 2),
                      "variant_b_fwd_gb": round(bf / 1e9, 2),
                      "variant_b_bwd_gb": round(bb / 1e9, 2),
                      "variant_b_total_gb": round((bf + bb + pb) / 1e9, 2),
                      "measured_gb_batch512": 143.5,
                      "param_count_model": param_count(tensors),
                      "param_count_reference": PARAM_COUNT}))


if __name__ == "__main__":
    main()
