"""Layers / XLA fusions, the shared causal convolution: the share of its
roofline that the pass between the two projections of the gated
short-convolution mixers reaches, whatever implements it, in percent: the
least time the chip could take for a trained batch of it over the device
seconds a trained batch of the ops under the layers' ``short_conv`` scope
(the two elementwise gates and the causal depthwise convolution, forward
and backward, recomputation included).

The pass is bound by bytes: a position's channel does ``2 K + 2`` FLOP
forward against ``8`` bytes moved. Bytes (``least_bytes``) come from the
run's configuration and traffic files alone, so they are the same whatever
implements the pass: what must cross HBM once a direction, in the
configuration's ``dtype``, ``hidden_size`` channels a token: forward
``[B | C | x]`` read (3) and ``y`` written (1); backward ``[B | C | x]``
and ``dy`` read (3 + 1) and the gradient of ``[B | C | x]`` written (3):
11 a token a layer, over the batch's tokens and the ``conv`` layers held
(``layer_types`` at ``layers_held``). The second forward that ``remat =
block`` makes, the float32 insides and the taps are not counted, so the
share cannot pass 100 %. At the cell's sizes: 0.74 GB a layer a step, 0.9
ms at ``peaks.json``'s ``hbm_bytes_per_s``, 3.6 ms for four.

The seconds are ``scope_parts.part_ms_by_layer``'s: ops whose scope path has
``short_conv`` right under an outermost ``gated_conv.<key>``, over the
whole dispatches the trace holds, mean over the chips. Nothing to read
(None) where the program opens no such scope (before PR 38), where the
configuration is no lfm2_moe model's, or under ``scope_groups``' guard
(under 90 % of the scoped programs' op time mapped, or no
``program_scopes`` record). Moves train_img_per_s.
"""

import span_reduce

import scope_parts

_CONFIG_KEYS = ("hidden_size", "layer_types", "layers_held", "conv_L_cache",
                "dtype")
_TRAFFIC_KEYS = ("seq_len", "batch_size")
_ITEM_BYTES = {"bfloat16": 2, "float32": 4}


def conv_layers(config):
    return sum(1 for i in config["layers_held"]
               if config["layer_types"][i] == "conv")


def least_bytes(config, traffic):
    """The bytes a trained batch of the pass must move through HBM; None
    where a size is missing."""
    if any(k not in config for k in _CONFIG_KEYS) \
            or any(k not in traffic for k in _TRAFFIC_KEYS) \
            or config["dtype"] not in _ITEM_BYTES:
        return None
    tokens = traffic["batch_size"] * traffic["seq_len"]
    forward = 3 + 1                     # [B | C | x] in; y out
    backward = 3 + 1 + 3                # those and dy in; d[B | C | x] out
    return float(forward + backward) * config["hidden_size"] \
        * _ITEM_BYTES[config["dtype"]] * tokens * conv_layers(config)


def conv_ms_by_layer(run):
    """Device ms a trained batch under ``gated_conv.<key>/short_conv``, a
    layer each (``{"gated_conv.l0_conv": ms, ...}``)."""
    return scope_parts.part_ms_by_layer(run, "gated_conv", "short_conv")


def read(run):
    """One ``gated_conv_pass`` line before the result line gives the
    pass's ms a layer and the bytes counted."""
    moved = least_bytes(run.config, run.traffic)
    by_layer = conv_ms_by_layer(run)
    if moved is None or not by_layer:
        return None
    memory_s = moved / (run.chips * run.peak("hbm_bytes_per_s"))
    span_reduce.phase("gated_conv_pass", least_bytes=moved,
                      memory_bound_ms=1e3 * memory_s,
                      short_conv_ms_by_layer=by_layer)
    return 100.0 * memory_s / (sum(by_layer.values()) / 1e3)
