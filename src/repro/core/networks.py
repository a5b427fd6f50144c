"""Policy/value networks: MLP actor-critic + transformer-trunk adapter."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init


class MLPPolicy:
    """Actor-critic MLP. Discrete: categorical logits; continuous:
    tanh-gaussian (state-independent log-std) squashed into the action
    box `act_mid ± act_scale` — construct with `for_spec` so the bounds
    come from the env's EnvSpec instead of being hard-coded."""

    def __init__(self, obs_dim, n_actions=0, act_dim=1, hidden=(64, 64),
                 act_mid=0.0, act_scale=1.0):
        self.obs_dim = obs_dim
        self.n_actions = n_actions
        self.act_dim = act_dim
        self.hidden = hidden
        self.discrete = n_actions > 0
        self.act_mid = act_mid
        self.act_scale = act_scale

    @classmethod
    def for_spec(cls, spec, hidden=(64, 64)):
        """Build a policy matching an EnvSpec (repro.envs.spec): output
        head width and continuous action bounds read off the spec."""
        a = spec.action
        if a.discrete:
            return cls(spec.obs_dim, a.n, hidden=hidden)
        return cls(spec.obs_dim, 0, a.size, hidden=hidden,
                   act_mid=a.midpoint, act_scale=a.half_range)

    def init(self, key):
        sizes = (self.obs_dim,) + self.hidden
        ks = jax.random.split(key, len(sizes) + 2)
        p = {"layers": [
            {"w": dense_init(ks[i], (sizes[i], sizes[i + 1])),
             "b": jnp.zeros((sizes[i + 1],))}
            for i in range(len(sizes) - 1)]}
        out = self.n_actions if self.discrete else self.act_dim
        p["pi"] = {"w": dense_init(ks[-2], (sizes[-1], out), scale=0.01),
                   "b": jnp.zeros((out,))}
        p["v"] = {"w": dense_init(ks[-1], (sizes[-1], 1), scale=1.0),
                  "b": jnp.zeros((1,))}
        if not self.discrete:
            p["log_std"] = jnp.full((self.act_dim,), -0.5)
        return p

    def trunk(self, params, obs):
        h = obs
        for lay in params["layers"]:
            h = jnp.tanh(h @ lay["w"] + lay["b"])
        return h

    def apply(self, params, obs):
        """-> (pi_out, value). pi_out: logits (discrete) or mean."""
        h = self.trunk(params, obs)
        pi = h @ params["pi"]["w"] + params["pi"]["b"]
        v = (h @ params["v"]["w"] + params["v"]["b"])[..., 0]
        return pi, v

    # -- distributions -------------------------------------------------
    def _dist_sample(self, params, pi, key):
        """Draw (action, log_prob) from the head output `pi`."""
        if self.discrete:
            a = jax.random.categorical(key, pi)
            logp = jax.nn.log_softmax(pi)[
                ..., a] if pi.ndim == 1 else jnp.take_along_axis(
                jax.nn.log_softmax(pi), a[..., None], -1)[..., 0]
            return a, logp
        std = jnp.exp(params["log_std"])
        eps = jax.random.normal(key, pi.shape)
        a = pi + std * eps
        logp = (-0.5 * ((a - pi) / std) ** 2
                - jnp.log(std) - 0.5 * jnp.log(2 * jnp.pi)).sum(-1)
        return jnp.tanh(a) * self.act_scale + self.act_mid, logp

    def sample(self, params, obs, key):
        """-> (action, log_prob)."""
        pi, _ = self.apply(params, obs)
        return self._dist_sample(params, pi, key)

    def sample_value(self, params, obs, key):
        """-> (action, log_prob, value) from ONE forward pass — the
        rollout engine's hot path (rollout.py runs one trunk evaluation
        per env step instead of sample + apply)."""
        pi, v = self.apply(params, obs)
        a, logp = self._dist_sample(params, pi, key)
        return a, logp, v

    def log_prob(self, params, obs, action):
        pi, v = self.apply(params, obs)
        if self.discrete:
            lp = jnp.take_along_axis(jax.nn.log_softmax(pi),
                                     action[..., None].astype(jnp.int32),
                                     -1)[..., 0]
            ent = -jnp.sum(jax.nn.softmax(pi) * jax.nn.log_softmax(pi), -1)
            return lp, v, ent
        # invert the tanh squashing into the action box
        raw = jnp.arctanh(jnp.clip((action - self.act_mid)
                                   / self.act_scale, -0.999, 0.999))
        std = jnp.exp(params["log_std"])
        lp = (-0.5 * ((raw - pi) / std) ** 2
              - jnp.log(std) - 0.5 * jnp.log(2 * jnp.pi)).sum(-1)
        ent = (0.5 + 0.5 * jnp.log(2 * jnp.pi) +
               jnp.log(std)).sum() * jnp.ones_like(v)
        return lp, v, ent


class TrunkPolicy:
    """Any registry architecture as a policy trunk (survey §2 LLM-actor
    mapping): observation -> transformer -> policy/value heads, with
    attention routed through `repro.core.attention` (the flash-attention
    dispatcher) when `use_kernels` is on.

    Two observation modes, chosen by `for_spec` off the EnvSpec:
      * token mode (integer obs, `obs_dim=None`): the (..., ctx) int
        history embeds through the model's token table — the original
        adapter (examples/ppo_trunk_gridworld.py).
      * feature mode (float obs, `obs_dim=F`): each scalar feature
        becomes one sequence position via a learned per-feature affine
        lift `obs[..., i] * w[i] + b[i]` into d_model, bypassing the
        token table — so box-observation envs (cartpole, pendulum)
        train the same transformer trunk.
    Discrete heads emit logits; continuous heads reuse MLPPolicy's
    tanh-gaussian squashed into `act_mid ± act_scale`. `n_layers`,
    `vocab` and `experts_held` cut the architecture to one chip's
    share of a deployment (`ModelConfig.cut`)."""

    def __init__(self, arch="paper-drl-trunk", n_actions=4, ctx=8,
                 reduced=True, obs_dim=None, act_dim=1, act_mid=0.0,
                 act_scale=1.0, use_kernels=False, n_layers=0, vocab=0,
                 experts_held=None):
        from repro.configs import ModelConfig, get_config
        from repro.models import build_model
        from repro.models.model import ModelOpts
        cfg = arch if isinstance(arch, ModelConfig) else get_config(arch)
        cfg = (cfg.reduced() if reduced else cfg).cut(n_layers, vocab,
                                                      experts_held)
        self.lm = build_model(cfg, ModelOpts(dtype="float32", remat=False,
                                             use_kernels=use_kernels))
        self.n_actions = n_actions
        self.discrete = n_actions > 0
        self.features = obs_dim          # None => token-obs mode
        self.ctx = ctx if obs_dim is None else obs_dim
        self.obs_dim = self.ctx
        self.act_dim = act_dim
        self.act_mid = act_mid
        self.act_scale = act_scale

    @classmethod
    def for_spec(cls, spec, arch="paper-drl-trunk", reduced=True,
                 use_kernels=True, n_layers=0, vocab=0, experts_held=None):
        """Build a trunk policy matching an EnvSpec: integer obs run in
        token mode, float obs in feature mode; head width and continuous
        action bounds read off the spec (mirrors MLPPolicy.for_spec)."""
        a, o = spec.action, spec.observation
        kw = dict(arch=arch, reduced=reduced, use_kernels=use_kernels,
                  n_layers=n_layers, vocab=vocab, experts_held=experts_held)
        if jnp.issubdtype(jnp.dtype(o.dtype), jnp.integer):
            kw["ctx"] = spec.obs_dim
        else:
            kw["obs_dim"] = spec.obs_dim
        if a.discrete:
            return cls(n_actions=a.n, **kw)
        return cls(n_actions=0, act_dim=a.size, act_mid=a.midpoint,
                   act_scale=a.half_range, **kw)

    def init(self, key):
        k1, k2, k3, k4 = jax.random.split(key, 4)
        d = self.lm.cfg.d_model
        out = self.n_actions if self.discrete else self.act_dim
        p = {"lm": self.lm.init(k1),
             "pi": {"w": dense_init(k2, (d, out), scale=0.01),
                    "b": jnp.zeros((out,))},
             "v": {"w": dense_init(k3, (d, 1)), "b": jnp.zeros((1,))}}
        if self.features is not None:
            p["feat"] = {"w": dense_init(k4, (self.features, d)),
                         "b": jnp.zeros((self.features, d))}
        if not self.discrete:
            p["log_std"] = jnp.full((self.act_dim,), -0.5)
        return p

    def apply(self, params, obs):
        """obs: (..., ctx) int token history or (..., F) float features
        -> (pi_out, value); pi_out logits (discrete) or mean."""
        squeeze = obs.ndim == 1
        if squeeze:
            obs = obs[None]
        from repro.models.layers import (embed_tokens, apply_norm)
        if self.features is None:
            tok = obs.astype(jnp.int32) % self.lm.cfg.vocab
            x = embed_tokens(params["lm"]["embed"], tok, self.lm.cfg,
                             jnp.float32)
        else:
            x = (obs.astype(jnp.float32)[..., None]
                 * params["feat"]["w"] + params["feat"]["b"])  # (B, F, d)
        x, _, _ = self.lm._run_seq(params["lm"], x, jnp.int32(0), None, 0)
        h = apply_norm(params["lm"]["final_norm"], x,
                       self.lm.cfg.norm_eps)[:, -1]
        pi = h @ params["pi"]["w"] + params["pi"]["b"]
        v = (h @ params["v"]["w"] + params["v"]["b"])[..., 0]
        if squeeze:
            pi, v = pi[0], v[0]
        return pi, v

    # -- layer-wise ZeRO-3 partition hooks -----------------------------
    def partition_list(self, params):
        """Split a policy-params pytree into per-block ZeRO-3 entries:
        one per superblock of the scan stack + the non-block remainder
        (embed, final_norm, heads, feat, log_std). Accepts both the
        canonical stacked stack (leading (repeats,) dim) and the lazy
        list form a previous merge produced. Returns None when the
        trunk has no scan stack (repeats == 0) — the caller then uses
        the single-partition path."""
        lm = params.get("lm") if isinstance(params, dict) else None
        if not isinstance(lm, dict) or lm.get("stack") is None:
            return None
        stack = lm["stack"]
        if isinstance(stack, (list, tuple)):
            blocks = list(stack)
        else:
            blocks = [jax.tree_util.tree_map(lambda a: a[r], stack)
                      for r in range(self.lm.repeats)]
        rest = dict(params, lm=dict(lm, stack=None))
        return blocks + [rest]

    def merge_partition_list(self, entries, materialize=False):
        """Inverse of `partition_list`. `materialize=False` keeps the
        stack as a list of per-block pytrees — `_run_seq` then runs the
        blocks unrolled, so each block's all-gather is consumed and
        dropped before the next one materializes; `materialize=True`
        restacks into the canonical (repeats, ...) layout used by
        host/checkpoint forms."""
        blocks, rest = list(entries[:-1]), entries[-1]
        if materialize:
            stack = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *blocks)
        else:
            stack = blocks
        return dict(rest, lm=dict(rest["lm"], stack=stack))

    _dist_sample = MLPPolicy._dist_sample
    sample = MLPPolicy.sample
    sample_value = MLPPolicy.sample_value
    log_prob = MLPPolicy.log_prob


def make_policy(spec, policy="mlp", hidden=(64, 64), **trunk_kwargs):
    """Policy factory shared by the algorithm registry: `policy="mlp"`
    (the house actor-critic MLP, `hidden` widths) or `policy="trunk"`
    (the transformer trunk via TrunkPolicy.for_spec; `trunk_kwargs`
    forwards arch/reduced/use_kernels and the cut)."""
    if policy == "trunk":
        return TrunkPolicy.for_spec(spec, **trunk_kwargs)
    if policy != "mlp":
        raise ValueError(f"unknown policy {policy!r}: expected 'mlp' "
                         f"or 'trunk'")
    return MLPPolicy.for_spec(spec, hidden)
