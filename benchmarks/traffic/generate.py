"""The one general traffic generator. A traffic mix is a data file
``benchmarks/traffic/<name>.json``; this module reads it and makes the raw
samples of its ``scene`` from its ``data_seed``. A training job's data set is
fixed while its initialization and order vary, so the samples depend on the
mix alone and ``--seed`` seeds weights, order and step keys (the drivers).

Scenes (adapted copies of the program's offline generators, kept here so a
later PR cannot change the yardstick's inputs; originals:
``scripts/generate_fluid_synthetic.py`` and ``distegnn_tpu/data/nbody_sim.py``):

``fluid_cloud``     ``graphs_pool`` independent particle clouds in the
                    Fluid113K record layout: uniform in a box at a density of
                    ``neighbours`` particles within ``radius``, damped falling
                    dynamics for ``delta_t`` frames between input and target.
                    Speeds grow with height by the factor ``1 + shear * z /
                    side`` (a column that collapses from the top), so that
                    the rows of a graph differ across the cloud and a loss
                    taken over part of them is a different loss.
``charged_bodies``  ``samples_train`` systems of ``n_bodies`` charged
                    particles (softened Coulomb force, clipped, symplectic
                    Euler), one sub-step a frame; input is frame ``frame_0``,
                    target frame ``frame_T``. Initial speeds are uniform in
                    ``speed_range`` from sample to sample (the published
                    generator fixes 0.5), so that the graphs of a batch
                    differ in how far they move. Made on the device in one
                    jitted call.
"""

from __future__ import annotations

import numpy as np


def fluid_cloud(mix: dict) -> list:
    """``[{loc, vel, target, viscosity, mass}]``, one per pool graph."""
    n, radius = int(mix["particles"]), float(mix["radius"])
    side = (n * (4.0 / 3.0) * np.pi * radius ** 3 / float(mix["neighbours"])) ** (1.0 / 3.0)
    gravity = np.array([0.0, 0.0, -0.05], np.float32)
    out = []
    for i in range(int(mix["graphs_pool"])):
        rng = np.random.default_rng([int(mix["data_seed"]), i])
        pos = rng.uniform(0, side, size=(n, 3)).astype(np.float32)
        vel = rng.normal(size=(n, 3)).astype(np.float32) * 0.01
        vel *= (1.0 + float(mix["shear"]) * pos[:, 2:3] / side).astype(np.float32)
        frames = []
        for _ in range(int(mix["delta_t"]) + 1):
            vel = (0.99 * vel + gravity * 0.01
                   + rng.normal(size=(n, 3)).astype(np.float32) * 1e-3)
            pos = pos + vel * 0.01
            out_of_box = (pos < 0) | (pos > side)
            vel = np.where(out_of_box, -0.5 * vel, vel)
            pos = np.clip(pos, 0, side)
            frames.append((pos.copy(), vel.copy()))
        out.append({"loc": frames[0][0], "vel": frames[0][1],
                    "target": frames[-1][0],
                    "viscosity": np.float32(0.01), "mass": np.float32(0.1)})
    return out


def charged_bodies(mix: dict) -> dict:
    """``{loc [S,N,3], vel, charges [S,N,1], target}`` as host float32."""
    import jax
    import jax.numpy as jnp

    S, N = int(mix["samples_train"]), int(mix["n_bodies"])
    f0, fT = int(mix["frame_0"]), int(mix["frame_T"])
    dt = float(mix["frame_dt"])
    chunk = min(S, 250)
    if S % chunk:
        raise ValueError(f"samples_train {S} is not a multiple of {chunk}")
    loc_std = (N / 5.0) ** (1.0 / 3.0) + 0.1
    max_f = 0.1 / dt

    def force(x, cc):
        d = x[:, :, None, :] - x[:, None, :, :]                  # [s,N,N,3]
        r2 = jnp.sum(d * d, axis=-1, keepdims=True) + 1e-2
        f = jnp.sum(cc[..., None] * d / (r2 * jnp.sqrt(r2)), axis=2)
        return jnp.clip(f, -max_f, max_f)

    def simulate(key):
        k1, k2, k3, k4 = jax.random.split(key, 4)
        c = jnp.where(jax.random.bernoulli(k1, 0.5, (chunk, N, 1)), 1.0, -1.0)
        x = jax.random.normal(k2, (chunk, N, 3)) * loc_std
        v = jax.random.normal(k3, (chunk, N, 3))
        lo, hi = (float(a) for a in mix["speed_range"])
        speed = jax.random.uniform(k4, (chunk, 1, 1), minval=lo, maxval=hi)
        v = speed * v / jnp.linalg.norm(v, axis=-1, keepdims=True)
        cc = c * jnp.swapaxes(c, 1, 2)

        def step(_, xv):
            x, v = xv
            v = v + dt * force(x, cc)
            return x + dt * v, v

        x0, v0 = jax.lax.fori_loop(0, f0, step, (x, v))
        xT, _ = jax.lax.fori_loop(0, fT - f0, step, (x0, v0))
        return x0, v0, c, xT

    keys = jax.random.split(jax.random.PRNGKey(int(mix["data_seed"])), S // chunk)
    x0, v0, c, xT = jax.jit(lambda ks: jax.lax.map(simulate, ks))(keys)
    host = lambda a: np.asarray(a, np.float32).reshape((S,) + a.shape[2:])
    return {"loc": host(x0), "vel": host(v0), "charges": host(c), "target": host(xT)}


SCENES = {"fluid_cloud": fluid_cloud, "charged_bodies": charged_bodies}


def make_samples(mix: dict):
    return SCENES[mix["scene"]](mix)
