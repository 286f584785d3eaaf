"""Diffusion process substrate: noise schedule, q_sample, DDIM/PLMS samplers.

The samplers drive a generic ``denoise_fn(x_t, t, labels) -> eps_hat``;
Ditto wraps that callable with temporal-difference processing (the
iterative sampler loop is exactly the temporal axis the paper exploits).
Each sampler step is a ``diffusion.step`` host span (:mod:`repro.core.spans`).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .spans import span


@dataclasses.dataclass(frozen=True)
class NoiseSchedule:
    betas: jnp.ndarray  # (T,)

    @property
    def alphas(self):
        return 1.0 - self.betas

    @property
    def alpha_bars(self):
        return jnp.cumprod(self.alphas)

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


def ddim_sample(sched: NoiseSchedule, denoise_fn, x_T, *, steps: int, labels=None, callback=None):
    """Full DDIM sampling loop (python loop: each step may change execution
    mode under Ditto/Defo, which is the point of the paper)."""
    ts = ddim_timesteps(sched.T, steps)
    x = x_T
    for i in range(len(ts)):
        with span("diffusion.step", step=i):
            t = int(ts[i])
            t_prev = int(ts[i + 1]) if i + 1 < len(ts) else -1
            t_vec = jnp.full((x.shape[0],), t, jnp.int32)
            eps_hat = denoise_fn(x, t_vec, labels)
            x = ddim_step(sched, x, eps_hat, t, t_prev)
            if callback is not None:
                callback(step_index=i, t=t, x=x)
    return x


def plms_sample(sched: NoiseSchedule, denoise_fn, x_T, *, steps: int, labels=None, callback=None):
    """Pseudo linear multistep (PLMS, arXiv:2202.09778) — SDM's sampler."""
    ts = ddim_timesteps(sched.T, steps)
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
