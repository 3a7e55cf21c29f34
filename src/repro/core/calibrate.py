"""Width calibration for the static in-collective codec (paper §3.4).

The paper amortizes ANS-table metadata by observing that float tensor
distributions are *stable across training steps* (Fig. 12), transmitting the
table once and reusing it.  We push the same observation one level deeper:
the packed-width ``W`` and exception capacity are chosen *offline* (or on
the first steps) from observed exponent statistics, then baked into the
compiled step as static wire sizes.  Periodic revalidation detects drift;
the in-wire ``overflow`` flag catches violations exactly (packing.py).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import codec, packing


@dataclasses.dataclass(frozen=True)
class WidthChoice:
    width: int
    exc_frac: float
    est_exc_rate: float  # fraction of blocks expected to escape
    est_ratio: float  # predicted wire ratio vs raw
    entropy_bits: float  # ANS floor for reference


def block_range_stats(x: jax.Array, block: int = 512) -> jax.Array:
    """Per-block max code values under the zero-escape mapping (int32):
    ``max_nz - min_nz + 1`` over nonzero exponents (0 for all-zero blocks).
    A block packs losslessly at width W iff its stat < 2**W."""
    exp, _ = codec.split_planes(x)
    return _exp_range_stats(exp, block)


def _exp_range_stats(exp: jax.Array, block: int) -> jax.Array:
    """:func:`block_range_stats` of a uint8 exponent plane."""
    exp = packing._pad_to(exp, block)
    b = exp.reshape(-1, block)
    nz = b != 0
    base = jnp.min(jnp.where(nz, b, jnp.uint8(255)), axis=-1).astype(jnp.int32)
    mx = jnp.max(jnp.where(nz, b, jnp.uint8(0)), axis=-1).astype(jnp.int32)
    return jnp.where(jnp.any(nz, axis=-1), mx - base + 1, 0)


def width_cost_curve(
    x: jax.Array,
    *,
    block: int = 512,
    max_exc_frac: float = 0.02,
) -> tuple:
    """The full predicted cost curve: one :class:`WidthChoice` per candidate
    exponent width ``1..exp_bits`` (escape rate and wire ratio AT that
    width).  :func:`choose_width` picks from this curve; the regret
    analytics (``obs/regret.py``) score achieved-vs-optimal widths with it.
    """
    exp, _ = codec.split_planes(x)
    return _exp_cost_curve(exp, codec.layout_of(x.dtype), block,
                           max_exc_frac)


def _exp_cost_curve(exp: jax.Array, lay: codec.FloatLayout, block: int,
                    max_exc_frac: float) -> tuple:
    """:func:`width_cost_curve` of a uint8 exponent plane."""
    rngs = np.asarray(_exp_range_stats(exp, block))
    ent = float(codec.exponent_entropy_bits(exp, lay.exp_bits))
    n_blocks = len(rngs)
    cap = packing.exception_capacity(n_blocks, max_exc_frac)
    curve = []
    for w in range(1, lay.exp_bits + 1):
        ratio = (
            lay.lo_bits
            + w
            + 8.0 / block  # bases
            + (cap * (4 + block) * 8.0) / (n_blocks * block)  # exceptions
        ) / lay.total_bits
        curve.append(WidthChoice(
            width=w,
            exc_frac=max_exc_frac,
            est_exc_rate=float(np.mean(rngs >= (1 << w))),
            est_ratio=ratio,
            entropy_bits=ent,
        ))
    return tuple(curve)


def choose_width(
    x: jax.Array,
    *,
    block: int = 512,
    target_exc_rate: float = 1e-3,
    margin_bits: int = 0,
    max_exc_frac: float = 0.02,
) -> WidthChoice:
    """Smallest W such that the expected escape rate stays under target.

    ``margin_bits`` adds headroom for distribution drift between calibration
    and use (the paper's stability claim says drift is small; we don't rely
    on it for correctness, only for speed).
    """
    curve = width_cost_curve(x, block=block, max_exc_frac=max_exc_frac)
    return _pick_width(curve, target_exc_rate, margin_bits)


def _pick_width(curve: tuple, target_exc_rate: float,
                margin_bits: int = 0) -> WidthChoice:
    for c in curve:
        if c.est_exc_rate <= target_exc_rate or c.width == curve[-1].width:
            return curve[min(c.width + margin_bits, curve[-1].width) - 1]
    raise AssertionError("unreachable: the last width always matches")


def choose_delta_widths(
    x: jax.Array, base: jax.Array, *, block: int = 512,
    target_exc_rate: float = 1e-3, max_exc_frac: float = 0.02,
) -> tuple:
    """Calibrate the XOR-delta wire's (exp_width, lo_width) from live data.

    ``x``/``base`` are two consecutive weight versions (or representative
    twins).  The exponent-delta width reuses :func:`choose_width` on the
    delta bit pattern; the lo width is the smallest W whose per-ELEMENT
    escape rate stays under half the exception capacity (the lo packer
    escapes per element, not per block — the XOR carry tail is heavy but
    element-local).  Store the result in
    ``CompressionProfile.widths["delta"/"delta_lo"]`` to drive
    ``CompressionPolicy.delta_widths``."""
    lay = codec.layout_of(x.dtype)
    # the delta stays a bit pattern: as a float it is mostly subnormal
    exp, lo = codec.split_bits(
        codec.xor_bits(x.reshape(-1), base.reshape(-1)), lay)
    w_exp = _pick_width(_exp_cost_curve(exp, lay, block, max_exc_frac),
                        target_exc_rate).width
    lo = np.asarray(lo.astype(jnp.uint32))
    budget = max_exc_frac / 2  # leave half the capacity as drift headroom
    w_lo = lay.lo_bits
    for w in range(1, lay.lo_bits + 1):
        if float(np.mean(lo >= (1 << w))) <= budget:
            w_lo = w
            break
    return int(w_exp), int(w_lo)


@dataclasses.dataclass(frozen=True)
class CompressionProfile:
    """Calibrated parameters per tensor class, reusable across steps.

    Tensor classes follow the paper's Table 1: gradients / weights /
    activations have distinct but individually-stable distributions.
    """

    widths: dict  # class name -> width
    block: int = 512
    exc_frac: float = 0.02
    # extra exponent-width headroom for the all-gather phase of the two-shot
    # (the reduced-sum distribution); 0 = trust exceptions, calibratable.
    ag_extra_bits: int = 0

    @staticmethod
    def default(dtype_name: str = "bfloat16") -> "CompressionProfile":
        # Conservative defaults validated on normalized-tensor workloads;
        # per-run calibration (calibrate_tree) overrides them.
        base = {"bfloat16": 5, "float32": 5, "float16": 4,
                "float8_e4m3fn": 4, "float8_e5m2": 4}[dtype_name]
        return CompressionProfile(
            widths={"gradient": base, "weight": base, "activation": base}
        )

    def width_for(self, tensor_class: str) -> int:
        return self.widths.get(tensor_class, max(self.widths.values()))


def calibrate_tree(
    tree, *, tensor_class: str = "gradient", block: int = 512, **kw
) -> CompressionProfile:
    """Calibrate one width per tensor class from a pytree of live tensors
    (e.g. the first step's gradients)."""
    leaves = [l for l in jax.tree_util.tree_leaves(tree) if hasattr(l, "dtype")]
    widths = [
        choose_width(l, block=block, **kw).width
        for l in leaves
        if jnp.dtype(l.dtype).name in codec.LAYOUTS
    ]
    w = max(widths) if widths else 8
    return CompressionProfile(widths={tensor_class: w}, block=block)
