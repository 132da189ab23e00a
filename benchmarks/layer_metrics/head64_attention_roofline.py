"""Pallas kernels: the share of the chip's bf16 peak that the causal core
of grouped-query attention at heads of 64 features reaches, in percent:
the core's useful FLOPs a trained batch over the device seconds a trained
batch of the ops under the layers' ``core`` scope (the fused kernel's
calls, forward and backward, the sums of a group's key/value gradients,
and what surrounds them there) times ``peaks.json``'s
``bf16_flops_per_s``.

The FLOPs come from the run's configuration and traffic files alone, so
they are the same whichever lowering of the 64-wide heads implements the
core: the two products of an attention, ``q k^T`` and ``p v`` over
``hidden_size / num_attention_heads`` features each, 2 FLOP a
multiply-accumulate, for ``num_attention_heads`` heads, over the causal
triangle ``time (time + 1) / 2`` of every ``full_attention`` layer held
(``layer_types`` at ``layers_held``); training = 3 x forward. At the
cell's sizes 1.65 TFLOP a step. Recomputation, the masked part of the
diagonal's tiles and lanes of padding (a head of 64 fills half a tile's
lanes) are not counted, so the share cannot pass 100 %. The bound is
compute: the core, trained, moves about 0.25 GB of ``q``, ``k``, ``v``,
``o`` and their gradients once, 0.3 ms at the chip's bandwidth, against
8.4 ms of products at its peak.

The seconds are ``scope_parts.part_ms_by_layer``'s: ops whose scope path has
``core`` right under an outermost ``gqa_attention.<key>``, over the whole
dispatches the trace holds, mean over the chips. Nothing to read (None)
where the program opens no such scope, where the configuration is no
lfm2_moe model's (no ``layers_held``), or under ``scope_groups``' guard
(under 90 % of the scoped programs' op time mapped, or no
``program_scopes`` record). Moves train_img_per_s.
"""

import span_reduce

import scope_parts

_CONFIG_KEYS = ("num_attention_heads", "hidden_size", "layer_types",
                "layers_held")
_TRAFFIC_KEYS = ("seq_len", "batch_size")


def useful_flops(config, traffic):
    """The core's FLOPs a trained batch; None where a size is missing."""
    if any(k not in config for k in _CONFIG_KEYS) \
            or any(k not in traffic for k in _TRAFFIC_KEYS):
        return None
    t = traffic["seq_len"]
    layers = sum(1 for i in config["layers_held"]
                 if config["layer_types"][i] == "full_attention")
    # heads x a head's width is the hidden size: 2 products x 2 FLOP
    forward = traffic["batch_size"] * layers * t * (t + 1) / 2.0 \
        * 2 * 2.0 * config["hidden_size"]
    return 3.0 * forward


def core_ms_by_layer(run):
    """Device ms a trained batch under ``gqa_attention.<key>/core``, a
    layer each."""
    return scope_parts.part_ms_by_layer(run, "gqa_attention", "core")


def read(run):
    """One ``head64_attention_core`` line before the result line gives
    the core's ms a layer and the FLOPs counted."""
    flops = useful_flops(run.config, run.traffic)
    by_layer = core_ms_by_layer(run)
    if flops is None or not by_layer:
        return None
    span_reduce.phase("head64_attention_core", useful_flops=flops,
                      core_ms_by_layer=by_layer)
    return 100.0 * flops / (sum(by_layer.values()) / 1e3 * run.chips
                            * run.peak("bf16_flops_per_s"))
