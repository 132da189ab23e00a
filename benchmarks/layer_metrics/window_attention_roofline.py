"""Pallas kernels: the share of the chip's bf16 peak that the causal core
of the grouped-query attention layers reaches, in percent: the core's
useful FLOPs a trained batch over the device seconds a trained batch of
the ops under the layers' ``core`` scope (the fused kernel's calls,
forward and backward, the sums of a group's key/value gradients, and what
surrounds them there) times ``peaks.json``'s ``bf16_flops_per_s``.

The FLOPs come from the run's configuration and traffic files alone, so
they are the same whatever implements the core: the two products of an
attention, ``q k^T`` and ``p v`` over ``head_dim`` features each, 2 FLOP
a multiply-accumulate, for ``num_attention_heads`` heads, over the
query-key pairs a layer's mask lets through: a sliding layer ``sum_i
min(i + 1, sliding_window)``, a full layer ``time (time + 1) / 2``;
training = 3 x forward. Recomputation, the masked parts of the tiles the
band's edges cross and lanes of padding are not counted, so the share
cannot pass 100 %. The bound is compute: at 2 x 8,192 positions a sliding
layer's core, trained, moves about 0.7 GB of ``q``, ``k``, ``v``, ``o``
and their gradients once, 0.9 ms at the chip's bandwidth, against 7.3 ms
of products at its peak.

The seconds are ``scope_groups.walk``'s: ops whose scope path has
``core`` right under an outermost ``gqa_attention.<key>``, over the whole
dispatches the trace holds, mean over the chips. Nothing to read (None)
where the program opens no such scope (before PR 32), where the
configuration is no afmoe model's, or under ``scope_groups``' guard
(under 90 % of the scoped programs' op time mapped, or no
``program_scopes`` record). Moves train_img_per_s.
"""

from collections import defaultdict

import span_reduce

import scope_groups

_CONFIG_KEYS = ("num_attention_heads", "head_dim", "num_hidden_layers",
                "sliding_window", "layer_types")
_TRAFFIC_KEYS = ("seq_len", "batch_size")


def pairs(time, window):
    """Query-key pairs a head computes over one sequence."""
    w = min(window, time) if window else time
    return w * (w + 1) / 2.0 + (time - w) * w


def useful_flops(config, traffic):
    """The core's FLOPs a trained batch; None where a size is missing."""
    if any(k not in config for k in _CONFIG_KEYS) \
            or any(k not in traffic for k in _TRAFFIC_KEYS):
        return None
    t = traffic["seq_len"]
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    seen = sum(pairs(t, config["sliding_window"]
                     if kind == "sliding_attention" else 0)
               for kind in kinds)
    forward = traffic["batch_size"] * seen * 2 * 2.0 \
        * config["num_attention_heads"] * config["head_dim"]
    return 3.0 * forward


def core_ms_by_layer(run):
    """Device ms a trained batch under ``gqa_attention.<key>/core``, a
    layer each (``{"gqa_attention.l3_attn": ms, ...}``)."""
    ops = scope_groups.walk(run)
    if ops is None:
        return None
    out = defaultdict(float)
    for ms, path, _ in ops:
        if scope_groups.outer_kind(path) == "gqa_attention" \
                and scope_groups.inner_part(path).split("/")[0] == "core":
            out[next(c for c in map(scope_groups._core, path.split("/"))
                     if "." in c)] += ms
    return dict(out)


def read(run):
    """One ``window_attention_core`` line before the result line gives the
    core's ms a layer: a sliding layer's beside the full layer's."""
    flops = useful_flops(run.config, run.traffic)
    by_layer = core_ms_by_layer(run)
    if flops is None or not by_layer:
        return None
    span_reduce.phase("window_attention_core", useful_flops=flops,
                      core_ms_by_layer=by_layer)
    return 100.0 * flops / (sum(by_layer.values()) / 1e3 * run.chips
                            * run.peak("bf16_flops_per_s"))
