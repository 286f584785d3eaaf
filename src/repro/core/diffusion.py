"""Diffusion process substrate: noise schedule, q_sample, DDIM/PLMS samplers.

The samplers drive a generic ``denoise_fn(x_t, t, labels) -> eps_hat``;
Ditto wraps that callable with temporal-difference processing (the
iterative sampler loop is exactly the temporal axis the paper exploits).
Each sampler step is a ``diffusion.step`` host span (:mod:`repro.core.spans`).

A ``denoise_fn`` may also offer ``denoise_fn.segment_loop``: a callable
with a ``ready()`` method that runs the remaining steps of a DDIM sample in
one call, on the device (``dit_runner.make_denoise_fn``). ``ddim_sample``
hands it the rest of the trajectory once it is ready, with the sampler's
pure update (:func:`loop_update`), unless the caller passed a ``callback``,
which needs each step's ``x`` on the host. ``plms_sample`` keeps its host
loop: its multistep history would have to ride in the loop's carry.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from .spans import span


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class NoiseSchedule:
    """A pytree of two arrays, so a jitted sampler loop takes the schedule
    as an argument. ``alpha_bars`` (the cumulative product of the alphas)
    is computed once, when the schedule is built from its ``betas``."""
    betas: jnp.ndarray  # (T,)
    alpha_bars: jnp.ndarray | None = None  # (T,)

    def __post_init__(self):
        if self.alpha_bars is None:
            object.__setattr__(self, "alpha_bars", jnp.cumprod(self.alphas))

    @property
    def alphas(self):
        return 1.0 - self.betas

    @property
    def T(self) -> int:
        return self.betas.shape[0]


def linear_schedule(T: int = 1000, b0: float = 1e-4, b1: float = 2e-2) -> NoiseSchedule:
    return NoiseSchedule(jnp.linspace(b0, b1, T, dtype=jnp.float32))


def cosine_schedule(T: int = 1000, s: float = 8e-3) -> NoiseSchedule:
    t = jnp.arange(T + 1, dtype=jnp.float32) / T
    f = jnp.cos((t + s) / (1 + s) * jnp.pi / 2) ** 2
    abar = f / f[0]
    betas = jnp.clip(1 - abar[1:] / abar[:-1], 1e-6, 0.999)
    return NoiseSchedule(betas)


def q_sample(sched: NoiseSchedule, x0, t, eps):
    """Forward process: x_t = sqrt(abar_t) x0 + sqrt(1-abar_t) eps."""
    abar = sched.alpha_bars[t]
    shape = (-1,) + (1,) * (x0.ndim - 1)
    return jnp.sqrt(abar).reshape(shape) * x0 + jnp.sqrt(1 - abar).reshape(shape) * eps


def ddim_timesteps(T: int, steps: int) -> jnp.ndarray:
    """Descending subset of timesteps for DDIM (e.g. T=1000, steps=50)."""
    stride = max(T // steps, 1)
    ts = jnp.arange(0, T, stride)[:steps]
    return ts[::-1]  # T-ish ... 0


def ddim_step(sched: NoiseSchedule, x_t, eps_hat, t, t_prev, *, eta: float = 0.0):
    """One deterministic DDIM update x_t -> x_{t_prev}."""
    abar_t = sched.alpha_bars[t]
    abar_p = jnp.where(t_prev >= 0, sched.alpha_bars[jnp.maximum(t_prev, 0)], 1.0)
    x0_pred = (x_t - jnp.sqrt(1 - abar_t) * eps_hat) / jnp.sqrt(abar_t)
    dir_xt = jnp.sqrt(1 - abar_p) * eps_hat
    return jnp.sqrt(abar_p) * x0_pred + dir_xt


def loop_update(sampler: str, sched: NoiseSchedule):
    """``update(x_t, eps_hat, t, t_prev) -> x_{t_prev}``, the sampler's step
    as a pytree callable (``jax.tree_util.Partial``): pure in ``t`` and
    ``t_prev`` (traced ints are fine), with the schedule's arrays as its
    leaves, so a jitted loop takes it as an argument and one trace serves
    any schedule of the same length. ``None`` for a sampler that keeps its
    host loop (PLMS)."""
    if sampler != "ddim":
        return None
    return jax.tree_util.Partial(ddim_step, sched)


def ddim_sample(sched: NoiseSchedule, denoise_fn, x_T, *, steps: int, labels=None, callback=None):
    """Full DDIM sampling loop (python loop: each step may change execution
    mode under Ditto/Defo, which is the point of the paper). Once the
    denoiser's ``segment_loop`` is ready, the remaining steps run there."""
    ts = np.asarray(ddim_timesteps(sched.T, steps))
    update = loop_update("ddim", sched)
    loop = getattr(denoise_fn, "segment_loop", None) if callback is None else None
    x = x_T
    for i in range(len(ts)):
        if loop is not None and loop.ready():
            return loop(x, ts[i:], labels, update)
        with span("diffusion.step", step=i):
            t = int(ts[i])
            t_prev = int(ts[i + 1]) if i + 1 < len(ts) else -1
            t_vec = jnp.full((x.shape[0],), t, jnp.int32)
            eps_hat = denoise_fn(x, t_vec, labels)
            x = update(x, eps_hat, t, t_prev)
            if callback is not None:
                callback(step_index=i, t=t, x=x)
    return x


def plms_sample(sched: NoiseSchedule, denoise_fn, x_T, *, steps: int, labels=None, callback=None):
    """Pseudo linear multistep (PLMS, arXiv:2202.09778) — SDM's sampler."""
    ts = np.asarray(ddim_timesteps(sched.T, steps))
    x = x_T
    eps_hist: list = []
    for i in range(len(ts)):
        with span("diffusion.step", step=i):
            t = int(ts[i])
            t_prev = int(ts[i + 1]) if i + 1 < len(ts) else -1
            t_vec = jnp.full((x.shape[0],), t, jnp.int32)
            eps = denoise_fn(x, t_vec, labels)
            if len(eps_hist) == 0:
                eps_prime = eps
            elif len(eps_hist) == 1:
                eps_prime = (3 * eps - eps_hist[-1]) / 2
            elif len(eps_hist) == 2:
                eps_prime = (23 * eps - 16 * eps_hist[-1] + 5 * eps_hist[-2]) / 12
            else:
                eps_prime = (55 * eps - 59 * eps_hist[-1] + 37 * eps_hist[-2]
                             - 9 * eps_hist[-3]) / 24
            eps_hist.append(eps)
            if len(eps_hist) > 3:
                eps_hist.pop(0)
            x = ddim_step(sched, x, eps_prime, t, t_prev)
            if callback is not None:
                callback(step_index=i, t=t, x=x)
    return x


SAMPLERS = {"ddim": ddim_sample, "plms": plms_sample}
