"""Raw camera video for the benchmark's traffic mixes.

A copy of the synthetic surveillance renderer of
``repro.sim.video_source`` (textured rectangles bouncing over a static
structured background), kept here so that a change to the program cannot
change the yardstick.  Two departures from the original:

* the objects are composited one at a time (a running max), so a
  1088x1920 chunk never materialises a (T, objects, H, W) plane;
* the texture frequency is a parameter of the mix (the original's
  0.8 rad/px was set for 96x160 frames).

Object positions, velocities, sizes, texture phases and the background
noise are drawn from the run's seed and the camera's index; the counts
and the ranges they are drawn from come from the traffic file, so every
seed gives the same amount of content in another arrangement.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

f32 = jnp.float32
CAMERAS, WEIGHTS = 0, 1     # uses of a run's seed (see seed_key)


@dataclasses.dataclass(frozen=True)
class Camera:
    """One camera's scene, as a traffic file states it."""
    n_objects: int
    min_size: float
    max_size: float
    speed: float                  # px / frame
    texture_contrast: float
    background_level: float
    texture_freq: float           # rad / px


def cameras(traffic: dict, n_cameras: int) -> list[Camera]:
    """The traffic file's ``cameras`` list, cycled over ``n_cameras``."""
    specs = traffic["cameras"]
    return [Camera(**specs[i % len(specs)]) for i in range(n_cameras)]


def seed_key(seed: int, *path: int):
    """A JAX key for one use of one run's seed (``path`` names the use):
    any non-negative seed, also one wider than 32 bits, maps to a key
    through numpy's SeedSequence."""
    word = np.random.SeedSequence([int(seed), *path]).generate_state(1)
    return jax.random.PRNGKey(int(word[0]) & 0x7FFFFFFF)


def _background(key, cam: Camera, H: int, W: int):
    yy = jnp.linspace(0, 1, H)[:, None]
    xx = jnp.linspace(0, 1, W)[None, :]
    base = cam.background_level + 25.0 * jnp.sin(6.28 * 2 * xx) + 15.0 * yy
    return base + jax.random.normal(key, (H, W), f32) * 4.0


def scene(key, cam: Camera, H: int, W: int) -> dict:
    """Seed-drawn object and background state of one camera."""
    N = cam.n_objects
    k1, k2, k3, k4, kbg = jax.random.split(key, 5)
    return dict(
        pos0=jax.random.uniform(k1, (N, 2), f32) * jnp.array([H, W], f32),
        vel=(jax.random.uniform(k2, (N, 2), f32) - 0.5) * 2 * cam.speed,
        size=jax.random.uniform(k3, (N, 2), f32)
        * (cam.max_size - cam.min_size) + cam.min_size,
        tex_phase=jax.random.uniform(k4, (N,), f32) * 6.28,
        bg=_background(kbg, cam, H, W),
    )


@partial(jax.jit, static_argnames=("n_frames", "contrast", "freq"))
def render(params: dict, t0, *, n_frames: int, contrast: float,
           freq: float):
    """(T, H, W) frames in [0, 255] of one camera from frame ``t0`` on:
    positions bounce off the walls by a triangular wave, as in the
    original renderer."""
    bg = params["bg"]
    H, W = bg.shape
    yy = jnp.arange(H, dtype=f32)[:, None]
    xx = jnp.arange(W, dtype=f32)[None, :]
    t = t0 + jnp.arange(n_frames, dtype=f32)

    def one_object(n, frames):
        size = params["size"][n]
        span = jnp.array([H, W], f32) - size
        raw = params["pos0"][n][None] + params["vel"][n][None] * t[:, None]
        period = 2 * jnp.maximum(span, 1.0)
        center = jnp.abs(jnp.mod(raw, period) - span) + size / 2   # (T, 2)
        ph = params["tex_phase"][n]
        tex = contrast * jnp.sign(jnp.sin(freq * yy + ph)
                                  * jnp.sin(freq * xx + ph))
        inside = ((jnp.abs(yy[None] - center[:, 0, None, None]) <= size[0] / 2)
                  & (jnp.abs(xx[None] - center[:, 1, None, None])
                     <= size[1] / 2))
        return jnp.maximum(frames, jnp.where(inside, 40.0 + jnp.abs(tex),
                                             0.0))

    obj = jax.lax.fori_loop(0, params["size"].shape[0], one_object,
                            jnp.zeros((n_frames, H, W), f32))
    return jnp.clip(bg[None] + obj, 0.0, 255.0)
