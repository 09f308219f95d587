"""Seeded stand-in for the UCI HIGGS data set (Baldi, Sadowski and
Whiteson, Nature Communications 5:4308, 2014), made on the device in one
jitted call and brought to host memory by the caller.

Each row has the published 28 float features in the published order:

* 21 low-level kinematic features: the lepton's transverse momentum,
  pseudorapidity and azimuth; the missing energy's magnitude and azimuth;
  then for each of four jets its transverse momentum, pseudorapidity,
  azimuth and b-tag.  Momenta are heavy-tailed and positive (log-normal
  around 1, as the published columns are scaled), pseudorapidities
  roughly normal, azimuths uniform with unit variance, b-tags discrete in
  {0, 1.087, 2.173};
* 7 high-level mass-like features (m_jj, m_jjj, m_lv, m_jlv, m_bb,
  m_wbb, m_wwbb): positive, log-normal around 1, narrower for the signal
  where the published masses peak (m_bb, m_wbb, m_wwbb).

Labels are +1 (signal) with probability 0.53, else -1.  A shared event
scale multiplies every momentum and mass, so the columns correlate as in
the published data.  Each class shifts every feature only a little, so
the classes overlap: a kernel machine's held-out hinge loss stays well
above 0 (a separable stand-in reads a loss of 0 for any alpha near the
right one, and then tells no fault from the program).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

N_FEATURES = 28
SIGNAL_SHARE = 0.53
BTAG_LEVELS = (0.0, 1.0865, 2.1731)
# Column order of the 21 low-level features: (kind, object) per column.
LOW_LEVEL = (("pt", 0), ("eta", 0), ("phi", 0),          # lepton
             ("pt", 1), ("phi", 1),                      # missing energy
             ("pt", 2), ("eta", 2), ("phi", 2), ("btag", 2),
             ("pt", 3), ("eta", 3), ("phi", 3), ("btag", 3),
             ("pt", 4), ("eta", 4), ("phi", 4), ("btag", 4),
             ("pt", 5), ("eta", 5), ("phi", 5), ("btag", 5))
# (log-centre, log-width) of the 7 masses for background and signal.
MASS_BG = ((0.0, 0.45), (0.05, 0.40), (0.0, 0.15), (0.05, 0.30),
           (0.05, 0.50), (0.05, 0.40), (0.05, 0.40))
MASS_SIG = ((-0.02, 0.40), (0.02, 0.35), (0.0, 0.14), (0.0, 0.26),
            (0.0, 0.35), (0.0, 0.30), (0.0, 0.28))


@functools.partial(jax.jit, static_argnames=("n", "d", "stream"))
def higgs_like(key, n: int, d: int = N_FEATURES, stream: int = 0):
    """(x (n, 28) f32, y (n,) +-1) for this key; ``stream`` > 0 draws
    further rows of the same distribution from an independent stream."""
    if d != N_FEATURES:
        raise ValueError(f"HIGGS rows have {N_FEATURES} features, not {d}")
    if stream:
        key = jax.random.fold_in(key, stream)
    k_y, k_scale, k_pt, k_eta, k_phi, k_tag, k_mass = jax.random.split(key, 7)
    sig = jax.random.bernoulli(k_y, SIGNAL_SHARE, (n,))
    s = sig.astype(jnp.float32)[:, None]
    scale = jnp.exp(0.2 * jax.random.normal(k_scale, (n, 1)))

    n_obj = 6
    pt = scale * jnp.exp(-0.1 + 0.06 * s
                         + (0.55 - 0.05 * s)
                         * jax.random.normal(k_pt, (n, n_obj)))
    eta = (1.0 - 0.08 * s) * jax.random.normal(k_eta, (n, n_obj))
    phi = jnp.sqrt(3.0) * jax.random.uniform(k_phi, (n, n_obj),
                                             minval=-1.0, maxval=1.0)
    # b-tag level per jet: signal events hold two b jets, so tag more often.
    u = jax.random.uniform(k_tag, (n, n_obj))
    p0 = 0.62 - 0.12 * s
    tag = jnp.where(u < p0, BTAG_LEVELS[0],
                    jnp.where(u < p0 + 0.2, BTAG_LEVELS[1], BTAG_LEVELS[2]))
    kinds = {"pt": pt, "eta": eta, "phi": phi, "btag": tag}
    low = jnp.stack([kinds[kind][:, obj] for kind, obj in LOW_LEVEL], axis=1)

    bg, sg = jnp.asarray(MASS_BG), jnp.asarray(MASS_SIG)
    centre = jnp.where(sig[:, None], sg[:, 0], bg[:, 0])
    width = jnp.where(sig[:, None], sg[:, 1], bg[:, 1])
    mass = scale * jnp.exp(centre + width
                           * jax.random.normal(k_mass, (n, len(MASS_BG))))
    x = jnp.concatenate([low, mass], axis=1).astype(jnp.float32)
    return x, jnp.where(sig, 1.0, -1.0).astype(jnp.float32)

