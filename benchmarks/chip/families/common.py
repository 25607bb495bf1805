"""What every model family of the chip benchmark shares: seeding, the
quantization grid arithmetic (a copy, so the yardstick cannot move with the
program), calibration on the device, and the reference's error counts."""
from __future__ import annotations

import zlib
from dataclasses import dataclass
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

MENU = (2, 4, 8, 16)
INT_RANGES = {8: (-128, 127), 4: (-8, 7), 2: (-2, 1)}
BYTES = {"float32": 4, "bfloat16": 2}


@dataclass(frozen=True)
class Family:
    """One model family: the functions the harness finds by the config's
    ``family`` key."""
    layer_names: Callable
    weight_counts: Callable
    work: Callable
    init_weights: Callable
    make_inputs: Callable
    forward: Callable
    pooled_weights: Callable
    build_target: Callable


@dataclass
class Grids:
    """Post-calibration quantization grids, in the program's own form."""
    act_ranges: Dict[str, float]
    wclips: Dict[Tuple[str, int], float]
    wranges: Dict[str, float]


def seed_int(seed: int, tag: str) -> int:
    """A 31-bit integer drawn from (seed, tag); any whole-number seed."""
    state = np.random.SeedSequence([int(seed), zlib.crc32(tag.encode())])
    return int(state.generate_state(1)[0] & 0x7FFFFFFF)


def key(seed: int, tag: str):
    return jax.random.PRNGKey(seed_int(seed, tag))


def fold_shape(mix):
    """(rows, length) of the validation fold: subsets x rows each."""
    fold = mix["fold"]
    return fold["subsets"] * fold["rows"], fold["length"]


def fake_quant(x, scale, lo, hi):
    return jnp.clip(jnp.round(x / scale), lo, hi) * scale


FP8_MAX = 448.0                       # largest finite float8 e4m3fn


def rounder(dtype: str):
    """``x -> x`` rounded to ``dtype`` and back to float32. float8 e4m3 is
    scaled per tensor so that the tensor's max-abs maps to the format's
    largest value: a control that flushed small tensors to zero would fail
    for a reason no lower-precision path of the program would have."""
    if dtype == "float32":
        return lambda x: x.astype(jnp.float32)
    if dtype == "bfloat16":
        return lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    if dtype == "float8_e4m3fn":
        def fp8(x):
            x = x.astype(jnp.float32)
            s = jnp.max(jnp.abs(x)) / FP8_MAX
            s = jnp.where(s > 0, s, 1.0)
            return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
        return fp8
    raise KeyError(f"no rounding to {dtype!r}")


@dataclass(frozen=True)
class Precision:
    """Where the reference rounds: every matmul operand to ``operands``
    (products accumulated in float32 at ``Precision.HIGHEST``), and the
    activation stream wherever the configuration keeps it narrow to
    ``activations``. ``EXACT`` is float32 throughout."""
    operands: str = "float32"
    activations: str = "float32"
    dot: str = "highest"

    def mm(self):
        return rounder(self.operands)

    def dot_precision(self):
        """``HIGHEST``; ``"default"`` only for the witness in ``control.py``
        (the matmul in whatever passes the backend picks by default)."""
        return getattr(jax.lax.Precision, self.dot.upper())

    def act(self):
        return rounder(self.activations)


EXACT = Precision()


def precision_of(cfg, which: str) -> Precision:
    """The config's stated precision (``"reference"``) or the control's,
    one step below it (``"control"``)."""
    return Precision(**cfg["precision"][which])


def quant_triple(bits: int, clip_or_range: float):
    """(scale, lo, hi) of a menu precision; 16 bits is fixed point with
    integer bits sized to the range."""
    if bits == 16:
        int_bits = int(np.ceil(np.log2(max(clip_or_range, 1e-9))))
        return (2.0 ** -(15.0 - max(int_bits, 0)), -32768.0, 32767.0)
    lo, hi = INT_RANGES[bits]
    return (clip_or_range / hi, float(lo), float(hi))


def qp_rows(alloc, names, grids: Grids) -> np.ndarray:
    """(L, 6) float32 grid rows of one allocation, in layer order."""
    rows = np.empty((len(names), 6), np.float32)
    for i, name in enumerate(names):
        wb, ab = alloc[name]
        rows[i, :3] = quant_triple(
            wb, grids.wranges[name] if wb == 16 else grids.wclips[(name, wb)])
        rows[i, 3:] = quant_triple(ab, grids.act_ranges[name])
    return rows


# ------------------------------------------------------------ calibration

def calibrate(fam: Family, cfg, params, inputs):
    """One unquantized reference pass over the fold at highest precision:
    the teacher labels (its argmax at every position) and the expected
    activation ranges (median over rows of each MxV input's max-abs)."""
    def run(params, inputs):
        taps = {}
        logits = fam.forward(params, cfg, inputs, taps=taps)
        return jnp.argmax(logits, -1).astype(jnp.int32), taps

    labels, taps = jax.jit(run)(params, inputs)
    ranges = {k: float(np.median(np.asarray(v))) for k, v in taps.items()}
    return labels, ranges


def weight_grids(fam: Family, cfg, params, n_grid: int = 64):
    """MMSE clip per (layer, 2/4/8 bits) over the layer's pooled weights,
    searched on the device over ``n_grid`` fractions of the max-abs, and
    each layer's max-abs (the 16-bit fixed-point range)."""
    fracs = jnp.asarray(np.linspace(1.0 / n_grid, 1.0, n_grid), jnp.float32)

    def run(params):
        clips, ranges = {}, {}
        for name, w in fam.pooled_weights(params, cfg).items():
            w = w.astype(jnp.float32)
            absmax = jnp.max(jnp.abs(w))
            ranges[name] = absmax
            absmax = jnp.where(absmax > 0, absmax, 1.0)
            for bits in (2, 4, 8):
                lo, hi = INT_RANGES[bits]

                def err(frac, w=w, absmax=absmax, lo=lo, hi=hi):
                    q = fake_quant(w, absmax * frac / hi, lo, hi)
                    return jnp.mean(jnp.square(w - q))

                errs = jax.lax.map(err, fracs)
                clips[(name, bits)] = absmax * fracs[jnp.argmin(errs)]
        return clips, ranges

    clips, ranges = jax.jit(run)(params)
    return ({k: float(v) for k, v in clips.items()},
            {k: float(v) for k, v in ranges.items()})


# ------------------------------------------------------------ reference

def reference_scorer(fam: Family, cfg, params, inputs, labels,
                     n_subsets: int, prec: Precision):
    """``score(qp_rows) -> (S,) int wrong-position counts per subset, or
    None when a logit is not finite``: the reference's forward of one
    allocation over the whole fold at precision ``prec``, its argmax
    against the labels, summed per validation subset (the program's
    folded layout: subset s is rows [s*R, (s+1)*R))."""
    rows = labels.shape[0]

    @jax.jit
    def counts(params, inputs, labels, qp):
        logits = fam.forward(params, cfg, inputs, qp=qp, prec=prec)
        wrong = jnp.argmax(logits, -1) != labels
        return (jnp.sum(wrong.reshape(n_subsets, rows // n_subsets, -1),
                        axis=(1, 2)),
                jnp.sum(~jnp.isfinite(logits)))

    def score(qp):
        c, bad = counts(params, inputs, labels, jnp.asarray(qp))
        if int(bad):                    # no number: the pass has failed
            return None
        return np.asarray(c).astype(np.int64)

    return score
