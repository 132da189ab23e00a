"""Pallas kernels: the share of the chip's bf16 peak that the causal
core of the multi-head latent attention layers reaches, in percent: the
core's useful FLOPs a trained batch over the device seconds a trained
batch of the ops under the layers' ``core`` scope (the fused kernel's
calls, forward and backward, and what surrounds them there) times
``peaks.json``'s ``bf16_flops_per_s``.

The FLOPs come from the run's configuration and traffic files alone, so
they are the same whatever implements the core: the two products of a
causal attention, ``q k^T`` over ``qk_nope_head_dim + qk_rope_head_dim``
features and ``p v`` over ``v_head_dim``, a query against the ``(time +
1) / 2`` keys it may see, 2 FLOP a multiply-accumulate, training = 3 x
forward; recomputation, the tiles the diagonal crosses computed in full
and lanes of padding are not counted, so the share cannot pass 100 %.
The bound is compute: at 2 x 8,192 positions a layer's core, trained,
needs about 1.1 GB of ``q``, ``k``, ``v``, ``o`` and their gradients
moved once, 1.4 ms at the chip's bandwidth, against 10.5 ms of products
at its peak.

The seconds are ``scope_groups.walk``'s: ops whose scope path has
``core`` right under an outermost ``mla_attention.<key>``, over the
whole dispatches the trace holds, mean over the chips. Nothing to read
(None) where the program opens no such scope (before PR 29), where the
configuration is no attention model's, or under ``scope_groups``' guard
(under 90 % of the scoped programs' op time mapped, or no
``program_scopes`` record). Moves train_img_per_s.
"""

import scope_groups

_CONFIG_KEYS = ("num_attention_heads", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "num_hidden_layers")
_TRAFFIC_KEYS = ("seq_len", "batch_size")


def useful_flops(config, traffic):
    """The core's FLOPs a trained batch; None where a size is missing."""
    if any(k not in config for k in _CONFIG_KEYS) \
            or any(k not in traffic for k in _TRAFFIC_KEYS):
        return None
    t = traffic["seq_len"]
    width = config["qk_nope_head_dim"] + config["qk_rope_head_dim"] \
        + config["v_head_dim"]
    forward = traffic["batch_size"] * config["num_hidden_layers"] * t \
        * 2.0 * config["num_attention_heads"] * width * (t + 1) / 2.0
    return 3.0 * forward


def core_ms(run):
    """Device ms a trained batch under ``mla_attention.<key>/core``."""
    ops = scope_groups.walk(run)
    if ops is None:
        return None
    return sum(ms for ms, path, _ in ops
               if scope_groups.outer_kind(path) == "mla_attention"
               and scope_groups.inner_part(path).split("/")[0] == "core")


def read(run):
    flops = useful_flops(run.config, run.traffic)
    ms = core_ms(run)
    if flops is None or not ms:
        return None
    return 100.0 * flops / (ms / 1e3 * run.chips
                            * run.peak("bf16_flops_per_s"))
