"""Layers / XLA fusions, the expert axis: the share of its roofline that
the expert layers' exchange reaches, in percent: the least time a chip's
interconnect could take to send what it must of a trained batch, over the
device seconds a trained batch of ``device_ms.expert_exchange``'s ops
(the all-to-alls and the packing under ``moe.<key>/exchange``).

The exchange is bound by bytes over ICI. What a chip must send
(``least_bytes``) comes from the run's configuration and traffic files
alone, whatever implements it: each of the chip's tokens (``batch_size x
seq_len`` over ``chips``) sends ``num_experts_per_tok`` rows, one a pick,
and in expectation ``(chips - 1) / chips`` of them go to another chip; a
row is ``hidden_size`` values of the configuration's ``dtype``; it crosses
four times a layer (dispatch and combine, forward and backward) in every
expert layer held (``mlp_layer_types`` at ``layers_held``). Padding rows
of the buffers, the experts' ids and the recomputed forward's exchange are
not counted, so the share cannot pass 100 %. At the cell's sizes: 49,152
rows of 4,608 B, 226.5 MB a pass a layer, 3.62 GB a chip a step, 18.1 ms
at ``peaks.json``'s ``ici_bits_per_s`` / 8.

Nothing to read (None) where the configuration spreads no experts over
the cell's chips (one chip, or no ``mlp_layer_types``), or where
``device_ms.expert_exchange`` reads nothing. Moves train_img_per_s.
"""

import os
import importlib.util

import span_reduce

_CONFIG_KEYS = ("hidden_size", "num_experts_per_tok", "mlp_layer_types",
                "layers_held", "dtype")
_TRAFFIC_KEYS = ("seq_len", "batch_size")
_ITEM_BYTES = {"bfloat16": 2, "float32": 4}


def _exchange_reader():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "device_ms.expert_exchange.py")
    spec = importlib.util.spec_from_file_location(
        "bench_device_ms_expert_exchange", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def expert_layers(config):
    return sum(1 for i in config["layers_held"]
               if config["mlp_layer_types"][i] == "sparse")


def least_bytes(config, traffic, chips):
    """The bytes one chip must send over ICI a trained batch; None where
    a size is missing or nothing crosses."""
    if chips < 2 or any(k not in config for k in _CONFIG_KEYS) \
            or any(k not in traffic for k in _TRAFFIC_KEYS) \
            or config["dtype"] not in _ITEM_BYTES:
        return None
    tokens = traffic["batch_size"] * traffic["seq_len"] / chips
    off_chip = tokens * config["num_experts_per_tok"] * (chips - 1) / chips
    passes = 2 * 2                      # dispatch, combine; both ways
    return off_chip * config["hidden_size"] \
        * _ITEM_BYTES[config["dtype"]] * passes * expert_layers(config)


def read(run):
    """One ``expert_exchange`` line before the result line gives the
    bytes counted, the interconnect's bound and the exchange's ms."""
    sent = least_bytes(run.config, run.traffic, run.chips)
    ops = _exchange_reader().exchange_ms(run)
    if sent is None or not ops:
        return None
    ms = sum(ops.values())
    bound_s = sent / (run.peak("ici_bits_per_s") / 8.0)
    span_reduce.phase("expert_exchange", least_bytes=sent,
                      ici_bound_ms=1e3 * bound_s, exchange_ms=ms)
    return 100.0 * bound_s / (ms / 1e3)
