"""Fused transmit-side encode: one-pass split+pack vs the three-pass
composition (paper §3.2 Step 1).

The unfused TPU encode materializes the split planes in HBM between
``codec.split_planes`` and the bit-plane pack — a write + re-read of
``2*(1+itemsize)`` bytes per element BEFORE anything reaches the wire.
The fused dispatch (``kernels/ops.encode_fused``) reads each input block
once and emits the packed wire directly.

Three sections:

1. MEASURED WireReport accounting: the real ``psum_compressed`` two-shot
   is traced over an abstract k-device mesh with the fused encode ON and
   OFF; the encode-side HBM bytes moved (input read + plane round-trip +
   wire write vs input read + wire write) come from those exact static
   records.  The headline number is the reduction factor — the acceptance
   gate asserts >= 2x.
2. Bit-parity + wall-clock of the fused vs unfused encode across dtypes
   and widths (CPU wall times serialize the jnp reference against the
   legacy composition — context only; the target metric is HBM traffic).
3. Ragged-tile dispatch: a non-tile-multiple shape runs the Pallas kernel
   (interpret mode on CPU) via pad-to-tile instead of degrading, and stays
   bit-identical.

Usage:
  python -m benchmarks.fig_encode            # full sweep
  python -m benchmarks.fig_encode --smoke    # <30 s CI-gate mode
"""
from __future__ import annotations

import argparse

from benchmarks.common import realistic_tensor, table, wall

SMOKE_BUDGET_S = 30  # enforced by benchmarks.run --smoke


def _abstract_mesh(k: int, name: str = "data"):
    from jax.sharding import AbstractMesh
    try:
        return AbstractMesh(((name, k),))
    except TypeError:  # newer ctor signature
        return AbstractMesh((k,), (name,))


def trace_encode_reports(k: int, n: int, dtype, *, fused_encode: bool):
    """WireReports of the real two-shot with the fused encode on/off."""
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.core import policy as policy_lib
    from repro.core.compressed_collectives import psum_compressed

    pol = policy_lib.CompressionPolicy(min_bytes=0, fused_encode=fused_encode)
    mesh = _abstract_mesh(k)
    policy_lib.clear_wire_reports()
    jax.eval_shape(
        jax.shard_map(
            lambda v: psum_compressed(v, "data", policy=pol),
            mesh=mesh, in_specs=(P(),), out_specs=(P(), P()),
            axis_names={"data"}, check_vma=False),
        jax.ShapeDtypeStruct((n,), dtype))
    reports = policy_lib.wire_reports()
    policy_lib.clear_wire_reports()
    return reports


def encode_hbm_moved(reports, k: int, itemsize: int) -> float:
    """Encode-side HBM bytes one device moves for these wires: the input
    read + the ENCODER'S OWN wire write (the all_gather report carries the
    k-times-gathered wire, so its local encode output is wire/k), plus the
    split-plane round-trip where the report says it was paid."""
    total = 0.0
    for r in reports:
        elems = r.encode_hbm_bytes / (2 * (1 + itemsize))  # encoded elems
        out = r.wire_bytes / (k if r.name == "all_gather" else 1)
        total += elems * itemsize + out
        if not r.encode_fused:
            total += r.encode_hbm_bytes
    return total


def run(k: int = 8, smoke: bool = False):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import codec, packing
    from repro.kernels import ops
    from repro.roofline.analysis import summarize_wire_reports

    # -- 1. measured encode-side HBM traffic (the acceptance metric) --------
    n = (1 << 18) if smoke else (1 << 22)
    rows, reductions = [], {}
    for dt in ([jnp.bfloat16, jnp.float32] if smoke
               else [jnp.bfloat16, jnp.float32, jnp.float16]):
        name = jnp.dtype(dt).name
        itemsize = jnp.dtype(dt).itemsize
        rep_f = trace_encode_reports(k, n, dt, fused_encode=True)
        rep_u = trace_encode_reports(k, n, dt, fused_encode=False)
        s_f = summarize_wire_reports(rep_f)
        s_u = summarize_wire_reports(rep_u)
        fused_moved = encode_hbm_moved(rep_f, k, itemsize)
        unfused_moved = encode_hbm_moved(rep_u, k, itemsize)
        assert s_f["encode_hbm_paid"] == 0 and s_u["encode_hbm_eliminated"] == 0
        reductions[name] = unfused_moved / fused_moved
        rows.append([
            name, f"{s_f['raw_bytes']/1e6:.2f}", f"{s_f['wire_bytes']/1e6:.2f}",
            f"{s_u['encode_hbm_paid']/1e6:.2f}",
            f"{unfused_moved/1e6:.2f}", f"{fused_moved/1e6:.2f}",
            f"{reductions[name]:.2f}x",
        ])
    table(f"Fused encode — measured encode-side HBM traffic "
          f"({n/1e6:.1f}M elems, psum_compressed two-shot, k={k})",
          ["dtype", "raw MB", "wire MB", "plane roundtrip MB",
           "unfused moved MB", "fused moved MB", "reduction"], rows)
    min_reduction = min(reductions.values())
    print(f"  encode-side HBM bytes moved: >= {min_reduction:.2f}x reduction "
          "across dtypes (acceptance gate: >= 2x)")

    # -- 2. bit-parity + CPU wall reference across dtypes/widths -------------
    n2 = (1 << 16) if smoke else (1 << 20)
    rows = []
    parity = True
    for dt in [jnp.bfloat16, jnp.float32]:
        lay = codec.layout_of(dt)
        for width in ([5] if smoke else [3, 5, 8]):
            x = realistic_tensor("gradient", n2, dt, seed=width)

            fused = jax.jit(lambda v: ops.encode_fused(
                v, width, use_pallas=False))

            @jax.jit
            def unfused(v):
                exp, lo = codec.split_planes(v)
                lo_pl = packing.bitplane_pack(
                    packing._pad_to(lo, packing.GROUP, "zero"), lay.lo_bits)
                pk = packing.pack_exponents(exp, width=width)
                return {"lo": lo_pl, "payload": pk.payload, "bases": pk.bases,
                        "exc_idx": pk.exc_idx, "exc_raw": pk.exc_raw,
                        "overflow": pk.overflow}

            a, b = fused(x), unfused(x)
            ok = all(bool(jnp.all(a[kk] == b[kk])) for kk in b)
            parity = parity and ok
            tf, tu = wall(fused, x), wall(unfused, x)
            rows.append([jnp.dtype(dt).name, width,
                         f"{tu*1e3:.1f}", f"{tf*1e3:.1f}",
                         "BIT-IDENTICAL" if ok else "MISMATCH"])
    table("Fused encode — parity + CPU wall reference (jnp paths; XLA may "
          "fuse both — HBM traffic above is the target metric)",
          ["dtype", "width", "unfused (ms)", "fused (ms)", "parity"], rows)

    # -- 3. ragged-tile Pallas dispatch (interpret mode off-TPU) -------------
    n3 = 512 * 8 + 600  # not a block or tile multiple
    x = realistic_tensor("gradient", n3, jnp.bfloat16, seed=1)
    a = ops.encode_fused(x, 5, use_pallas=True)
    b = ops.encode_fused(x, 5, use_pallas=False)
    ragged_ok = all(bool(jnp.all(a[kk] == b[kk])) for kk in b)
    parity = parity and ragged_ok
    print(f"  ragged-tile Pallas dispatch (n={n3}): pad-to-tile path "
          f"{'BIT-IDENTICAL' if ragged_ok else 'MISMATCH'} vs reference")

    assert min_reduction >= 2.0, min_reduction
    assert parity, "fused encode must be bit-identical to the composition"
    return {"reductions": reductions, "min_reduction": min_reduction,
            "parity": parity}


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small tensors — runs in <30 s")
    ap.add_argument("-k", type=int, default=8)
    args = ap.parse_args()
    run(k=args.k, smoke=args.smoke)
