"""Static placements: the all-slow baseline (paper Fig. 1 normalization) and
an oracle upper bound (true-count top-k, instant migration)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.baselines.protocol import (LegacyPolicyAdapter, PolicySpec,
                                      ranked_take)
from repro.utils.pytree import pytree_dataclass
from repro.utils.topk import top_k


@pytree_dataclass
class StaticState:
    t: jnp.ndarray            # i32


@pytree_dataclass
class AllSlowSpec(PolicySpec):
    name = "all-slow"

    def init(self, n_pages, k, machine):
        return StaticState(t=jnp.zeros((), jnp.int32))

    def observe(self, state, observed):
        return state.replace(t=state.t + 1)

    def fires(self, state):
        return jnp.asarray(False)

    def pad_promote(self, n, k):
        return 1

    def pad_demote(self, n, k):
        return 1

    def policy(self, state, slow_bw, app_bw, k):
        empty = jnp.full((1,), -1, jnp.int32)
        return state, empty, empty


@pytree_dataclass
class OracleState:
    in_fast: jnp.ndarray      # bool [n]
    last_obs: jnp.ndarray     # f32 [n] this interval's TRUE counts
    t: jnp.ndarray            # i32


@pytree_dataclass
class OracleSpec(PolicySpec):
    """Sees TRUE access counts and rebalances instantly — an upper bound on
    any sampling-based policy (migration traffic still charged)."""

    name = "oracle"
    wants_true_counts = True

    def pad_promote(self, n, k):
        return max(1, min(n, k))

    def pad_demote(self, n, k):
        return max(1, min(n, k))

    def init(self, n_pages, k, machine):
        return OracleState(
            in_fast=jnp.zeros((n_pages,), bool),
            last_obs=jnp.zeros((n_pages,), jnp.float32),
            t=jnp.zeros((), jnp.int32))

    def observe(self, state, observed):
        return state.replace(last_obs=observed, t=state.t + 1)

    def policy(self, state, slow_bw, app_bw, k):
        n = state.last_obs.shape[0]
        _, top = top_k(state.last_obs, k)     # desc, ties by index
        target = jnp.zeros((n,), bool).at[top].set(True)
        idx = jnp.arange(n, dtype=jnp.int32)
        promote, n_p = ranked_take(idx, target & ~state.in_fast,
                                   self.pad_promote(n, k))
        demote, _ = ranked_take(idx, ~target & state.in_fast,
                                self.pad_demote(n, k), n_p)
        return state.replace(in_fast=target), promote, demote


class AllSlowPolicy(LegacyPolicyAdapter):
    def __init__(self):
        super().__init__(AllSlowSpec())


class OraclePolicy(LegacyPolicyAdapter):
    def __init__(self):
        super().__init__(OracleSpec())
