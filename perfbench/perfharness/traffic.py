"""Traffic generation from ``--seed`` alone: spike rasters and arrival schedules.

The raster families are vectorised copies of ``repro.data.snn_datasets``
(``mnist_like``, ``dvs_like``) plus a plain Bernoulli raster. They run in one
jitted program per chunk of samples, on the device, so a window of tens of
thousands of distinct rasters costs a fraction of a second of set-up. The
copies keep the originals' distributions (glyph jitter, noise and pen gaps,
then Bernoulli rate coding; drifting gratings with per-sample angle,
wavelength, speed and phase), not their exact bits.

Arrivals are the Poisson process of ``benchmarks/serve_bench.py``
conditioned on its count: ``round(rate * seconds)`` arrival times drawn
uniformly over the window and sorted. The gaps are still exponential, and
every seed offers the same number of requests, so two seeds differ in the
order of the work and not in its amount.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 1024  # most samples per generator call; every chunk of a run reuses one program,
# and its scratch (a float32 wave and a uniform draw of [CHUNK, T, 256]) stays small

# 3x5 digit glyphs, rows of 3 bits: the font of repro.data.snn_datasets.
_FONT_3X5 = (
    ("111", "101", "101", "101", "111"),
    ("010", "110", "010", "010", "111"),
    ("111", "001", "111", "100", "111"),
    ("111", "001", "111", "001", "111"),
    ("101", "101", "111", "001", "001"),
    ("111", "100", "111", "001", "111"),
    ("111", "100", "111", "101", "111"),
    ("111", "001", "010", "010", "010"),
    ("111", "101", "111", "101", "111"),
    ("111", "101", "111", "001", "111"),
)


def _placed_glyphs() -> np.ndarray:
    """Every digit at every offset ``_glyph16`` can draw: ``[10, 2, 6, 16, 16]``.

    Each glyph is upsampled x3 to 15x9 and placed at row ``oy`` in {0, 1} and
    column ``ox`` in 0..5 (``2 + integers(-2, 4)``) of a 16x16 image.
    """
    table = np.zeros((10, 2, 6, 16, 16), np.float32)
    for d, rows in enumerate(_FONT_3X5):
        up = np.kron(np.array([[int(c) for c in r] for r in rows], np.float32), np.ones((3, 3)))
        for oy in range(2):
            for ox in range(6):
                table[d, oy, ox, oy : oy + 15, ox : ox + 9] = up
    return table


_GLYPHS = _placed_glyphs()


def seed_key(seed: int, stream: int) -> jax.Array:
    """A JAX key for one named stream of a run's seed (any non-negative int)."""
    word = np.random.SeedSequence([int(seed), int(stream)]).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), int(stream)]))


@functools.partial(jax.jit, static_argnames=("n", "T", "max_rate"))
def _mnist_chunk(key, n: int, T: int, max_rate: float):
    k_lab, k_oy, k_ox, k_amp, k_bg, k_drop, k_spk = jax.random.split(key, 7)
    labels = jax.random.randint(k_lab, (n,), 0, 10)
    oy = jax.random.randint(k_oy, (n,), 0, 2)  # 15 rows fit with 1 px slack
    ox = jax.random.randint(k_ox, (n,), 0, 6)  # 9 columns, 2 + (-2..3)
    img = jnp.asarray(_GLYPHS)[labels, oy, ox]  # [n, 16, 16]
    img = img * jax.random.uniform(k_amp, (n, 1, 1), minval=0.7, maxval=1.0)
    img = img + jax.random.uniform(k_bg, (n, 16, 16), maxval=0.08)
    img = img * (jax.random.uniform(k_drop, (n, 16, 16)) > 0.05)
    p = jnp.clip(jnp.clip(img, 0.0, 1.0).reshape(n, 1, 256) * max_rate, 0.0, 1.0)
    spikes = jax.random.uniform(k_spk, (n, T, 256)) < p
    return spikes.astype(jnp.uint8), labels.astype(jnp.int32)


def _dvs_classes(n_classes: int, class_seed: int):
    """Per-class grating parameters, drawn as ``dvs_like`` draws them."""
    rng = np.random.default_rng(class_seed)
    angles = rng.permutation(n_classes) * np.pi / n_classes
    wavelengths = 3.0 + rng.permutation(n_classes) % 4
    speeds = rng.uniform(0.15, 0.6, n_classes)
    phases = rng.uniform(0, 2 * np.pi, n_classes)
    return np.stack([angles, wavelengths, speeds, phases]).astype(np.float32)  # [4, C]


@functools.partial(jax.jit, static_argnames=("n", "T"))
def _dvs_chunk(key, classes, n: int, T: int):
    k_lab, k_ang, k_lam, k_spd, k_ph, k_spk = jax.random.split(key, 6)
    n_classes = classes.shape[1]
    labels = jax.random.randint(k_lab, (n,), 0, n_classes)
    angle, lam, speed, phase = (classes[i][labels] for i in range(4))
    angle = angle + 0.06 * jax.random.normal(k_ang, (n,))
    lam = lam * jax.random.uniform(k_lam, (n,), minval=0.95, maxval=1.05)
    speed = speed * jax.random.uniform(k_spd, (n,), minval=0.9, maxval=1.1)
    phase = phase + 0.3 * jax.random.normal(k_ph, (n,))
    yy, xx = jnp.meshgrid(jnp.arange(16.0), jnp.arange(16.0), indexing="ij")
    proj = (
        xx.reshape(1, 256) * jnp.cos(angle)[:, None] + yy.reshape(1, 256) * jnp.sin(angle)[:, None]
    )
    t = jnp.arange(T, dtype=jnp.float32)[None, :, None]
    wave = jnp.sin(
        2 * jnp.pi * proj[:, None, :] / lam[:, None, None]
        + phase[:, None, None]
        + speed[:, None, None] * t
    )
    p = 0.45 * (wave > 0.3) + 0.01
    spikes = jax.random.uniform(k_spk, (n, T, 256)) < p
    return spikes.astype(jnp.uint8), labels.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("n", "T", "n_in", "density"))
def _bernoulli_chunk(key, n: int, T: int, n_in: int, density: float):
    spikes = jax.random.uniform(key, (n, T, n_in)) < density
    return spikes.astype(jnp.uint8), jnp.zeros((n,), jnp.int32)


def rasters(spec: dict, n: int, T: int, n_in: int, seed: int, stream: int = 1):
    """``n`` distinct rasters ``uint8 [n, T, n_in]`` and labels ``int32 [n]``.

    ``spec`` is the traffic file's ``raster`` entry: ``{"family": "mnist_like",
    "max_rate": 0.35}``, ``{"family": "dvs_like", "n_classes": 11,
    "class_seed": 125}`` or ``{"family": "bernoulli", "density": 0.03}``.
    """
    family = spec["family"]
    if family in ("mnist_like", "dvs_like") and n_in != 256:
        raise ValueError(f"{family} rasters have 256 channels, the network takes {n_in}")
    key = seed_key(seed, stream)
    if family == "dvs_like":
        classes = jnp.asarray(_dvs_classes(spec.get("n_classes", 11), spec.get("class_seed", 125)))
    chunk = min(CHUNK, n)
    out_s, out_l = [], []
    for i in range(-(-n // chunk)):
        k = jax.random.fold_in(key, i)
        if family == "mnist_like":
            s, lab = _mnist_chunk(k, chunk, T, float(spec.get("max_rate", 0.35)))
        elif family == "dvs_like":
            s, lab = _dvs_chunk(k, classes, chunk, T)
        elif family == "bernoulli":
            s, lab = _bernoulli_chunk(k, chunk, T, n_in, float(spec["density"]))
        else:
            raise ValueError(f"unknown raster family {family!r}")
        out_s.append(np.asarray(s))
        out_l.append(np.asarray(lab))
    return np.concatenate(out_s)[:n], np.concatenate(out_l)[:n]


def poisson_arrivals(rate_per_s: float, seconds: float, seed: int, stream: int = 2):
    """Sorted arrival offsets in ``[0, seconds)``: ``round(rate * seconds)`` of them."""
    if rate_per_s <= 0 or seconds <= 0:
        raise ValueError(f"need a positive rate and window, got {rate_per_s}, {seconds}")
    n = max(1, int(round(rate_per_s * seconds)))
    return np.sort(seed_rng(seed, stream).uniform(0.0, seconds, n))
