"""Pallas kernels / scan: the share of its roofline that the gated delta
rule's scan along time reaches, whatever implements it, in percent: the
least time the chip could take for a trained batch of it over the device
seconds a trained batch of the ops under the layers' ``scan`` scope (the
chunk's triangular solve, the products inside and between chunks, the
states, forward and backward, recomputation included).

Operations and bytes come from the run's configuration and traffic files
alone, so they are the same whatever implements the scan.

FLOPs (``useful_flops``): the RECURRENCE's work, not the chunked form's:
a value head carries a state of ``linear_key_head_dim`` x
``linear_value_head_dim`` and a position does three products with it
(the decayed state read by the key, the key's write, the query's read),
2 FLOP a multiply-accumulate: ``6 dk dv`` a value head a position
forward; training = 3 x forward; over ``linear_num_value_heads`` heads,
the batch's tokens and the linear-attention layers held (layer ``i`` is
one iff ``(i + 1) % full_attention_interval != 0``).

Bytes (``least_bytes``): what a scan that keeps its state on the chip
must still move through HBM: forward it reads q, k (``linear_num_key_heads``
x dk each), v and writes o (``linear_num_value_heads`` x dv each) once;
backward it reads those four and ``dO`` and writes the three gradients
once; in the configuration's ``dtype``; g and beta (float32, one a value
head) beside them, read forward, read and their gradients written
backward.

The bound is the larger of FLOPs over ``peaks.json``'s
``bf16_flops_per_s`` and bytes over its ``hbm_bytes_per_s`` (at the
cell's sizes the bytes: 3.66 GB, 4.5 ms, against 0.46 TFLOP, 2.4 ms).
Recomputation, the chunked form's extra products and lanes of padding
are not counted, so the share cannot pass 100 %.

The seconds are ``scope_groups.walk``'s: ops whose scope path has ``scan``
right under an outermost ``gated_delta.<key>``, over the whole dispatches
the trace holds, mean over the chips. Nothing to read (None) where the
program opens no such scope (before PR 34), where the configuration is no
qwen3_next model's, or under ``scope_groups``' guard (under 90 % of the
scoped programs' op time mapped, or no ``program_scopes`` record). Moves
train_img_per_s.
"""

from collections import defaultdict

import span_reduce

import scope_groups

_CONFIG_KEYS = ("linear_num_key_heads", "linear_num_value_heads",
                "linear_key_head_dim", "linear_value_head_dim",
                "full_attention_interval", "num_hidden_layers", "dtype")
_TRAFFIC_KEYS = ("seq_len", "batch_size")
_ITEM_BYTES = {"bfloat16": 2, "float32": 4}


def _sized(config, traffic):
    return all(k in config for k in _CONFIG_KEYS) \
        and all(k in traffic for k in _TRAFFIC_KEYS) \
        and config["dtype"] in _ITEM_BYTES


def linear_layers(config):
    return sum(1 for i in range(config["num_hidden_layers"])
               if (i + 1) % config["full_attention_interval"])


def useful_flops(config, traffic):
    """The recurrence's FLOPs a trained batch; None where a size is
    missing."""
    if not _sized(config, traffic):
        return None
    tokens = traffic["batch_size"] * traffic["seq_len"]
    forward = 6.0 * config["linear_key_head_dim"] \
        * config["linear_value_head_dim"] * config["linear_num_value_heads"]
    return 3.0 * forward * tokens * linear_layers(config)


def least_bytes(config, traffic):
    """The bytes a trained batch of the scan must move through HBM; None
    where a size is missing."""
    if not _sized(config, traffic):
        return None
    tokens = traffic["batch_size"] * traffic["seq_len"]
    qk = config["linear_num_key_heads"] * config["linear_key_head_dim"]
    vo = config["linear_num_value_heads"] * config["linear_value_head_dim"]
    item = _ITEM_BYTES[config["dtype"]]
    forward = (2 * qk + 2 * vo) * item          # q, k, v in; o out
    backward = (2 * qk + 2 * vo) * item + vo * item \
        + (2 * qk + vo) * item                  # those, dO; dq, dk, dv
    gates = 2 * config["linear_num_value_heads"] * 4 * (1 + 2)
    return float(forward + backward + gates) * tokens * linear_layers(config)


def scan_ms_by_layer(run):
    """Device ms a trained batch under ``gated_delta.<key>/scan``, a
    layer each (``{"gated_delta.l0_delta": ms, ...}``)."""
    ops = scope_groups.walk(run)
    if ops is None:
        return None
    out = defaultdict(float)
    for ms, path, _ in ops:
        if scope_groups.outer_kind(path) == "gated_delta" \
                and scope_groups.inner_part(path).split("/")[0] == "scan":
            out[next(c for c in map(scope_groups._core, path.split("/"))
                     if "." in c)] += ms
    return dict(out)


def read(run):
    """One ``delta_scan`` line before the result line gives the scan's ms
    a layer, the two counts and which bounds."""
    flops = useful_flops(run.config, run.traffic)
    moved = least_bytes(run.config, run.traffic)
    by_layer = scan_ms_by_layer(run)
    if flops is None or not by_layer:
        return None
    compute_s = flops / (run.chips * run.peak("bf16_flops_per_s"))
    memory_s = moved / (run.chips * run.peak("hbm_bytes_per_s"))
    span_reduce.phase("delta_scan", useful_flops=flops, least_bytes=moved,
                      compute_bound_ms=1e3 * compute_s,
                      memory_bound_ms=1e3 * memory_s,
                      bound="memory" if memory_s >= compute_s else "compute",
                      scan_ms_by_layer=by_layer)
    return 100.0 * max(compute_s, memory_s) / (sum(by_layer.values()) / 1e3)
