"""Faults planted in the timed path of the LFM2 cell, to show
that ``correct`` catches them.  Each takes ``patch(obj, name, value)``
(``monkeypatch.setattr`` in the tests, ``setattr`` in
``bench/check_cell.py`` on the chip) and replaces one function of the
program with a broken one.

- ``dropped_at_capacity``: training and prefill drop each held expert's
  assignments past GShard's capacity (factor 1.25) in the order of the
  tokens, as the removed capacity path did;
- ``bias_ignored``: the expert bias is left out of the selection;
- ``conv_state_reset``: every decode step starts the short convolution
  from a zero state.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp


def dropped_at_capacity(patch):
    import repro.models.moe as moe_mod
    orig = moe_mod._grouped_experts

    def grouped(experts, cfg, kind, x2, top_w, top_e):
        T, K = top_e.shape
        cap = int(T * K / cfg.n_experts * 1.25)
        hot = (top_e.reshape(-1)[:, None]
               == jnp.arange(cfg.n_experts)).astype(jnp.int32)
        rank = (jnp.cumsum(hot, axis=0) - hot).reshape(T, K, -1)
        kept = jnp.take_along_axis(rank, top_e[..., None], -1)[..., 0] < cap
        return orig(experts, cfg, kind, x2, jnp.where(kept, top_w, 0.0),
                    jnp.where(kept, top_e, -1))
    patch(moe_mod, "_grouped_experts", grouped)


def bias_ignored(patch):
    import repro.models.moe as moe_mod
    orig = moe_mod.route

    def route(params, cfg, x2):
        return orig(params, dataclasses.replace(cfg, expert_bias=False), x2)
    patch(moe_mod, "route", route)


def conv_state_reset(patch):
    import repro.models.shortconv as sc_mod
    orig = sc_mod.apply_shortconv

    def apply(params, cfg, h, state=None, return_state=False):
        if state is not None and h.shape[1] == 1:
            state = {"conv": jnp.zeros_like(state["conv"])}
        return orig(params, cfg, h, state=state, return_state=return_state)
    patch(sc_mod, "apply_shortconv", apply)


FAULTS = {f.__name__: f for f in (dropped_at_capacity, bias_ignored,
                                  conv_state_reset)}
