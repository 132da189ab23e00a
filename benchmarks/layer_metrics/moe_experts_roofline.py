"""Pallas kernels: the share of the chip's bf16 peak that the routed
experts of the expert layers reach, in percent: the experts' useful
FLOPs a trained batch over the device seconds a trained batch of the
ops under the layers' ``experts`` scope (the gathers of rows, the
products, forward and backward, and the scatters of results, whatever
implements them) times ``peaks.json``'s ``bf16_flops_per_s``.

The FLOPs come from the run's configuration and traffic files alone, so
they are the same whatever implements the experts: a token's pick of a
held expert is a SwiGLU of three products of ``hidden_size`` x
``moe_intermediate_size``, 2 FLOP a multiply-accumulate, training = 3 x
forward; a token makes ``num_experts_per_tok`` picks of which the share
``n_routed_experts`` held of ``published.n_routed_experts`` lands here in
expectation, in each of the ``num_hidden_layers - first_k_dense_replace``
expert layers. The rows that pad an expert's share to whole blocks, the
second forward that recomputation a block runs and the backward's own
recomputation are not counted, so the share cannot pass 100 %. The
bound is compute: a layer's 12,288 rows in and out are 0.2 GB and its
eight experts' weights and gradients 0.28 GB, under a millisecond at
the chip's bandwidth, against 3.2 ms of products at its peak.

The seconds are ``scope_groups.walk``'s: ops whose scope path has
``experts`` right under an outermost ``moe.<key>``, over the whole
dispatches the trace holds, mean over the chips. Nothing to read (None)
where the program opens no such scope, where the configuration is no
expert model's, or under ``scope_groups``' guard (under 90 % of the
scoped programs' op time mapped, or no ``program_scopes`` record).
Moves train_img_per_s.
"""

import scope_groups

_CONFIG_KEYS = ("hidden_size", "moe_intermediate_size",
                "num_experts_per_tok", "n_routed_experts",
                "num_hidden_layers", "first_k_dense_replace")
_TRAFFIC_KEYS = ("seq_len", "batch_size")


def useful_flops(config, traffic):
    """The routed experts' FLOPs a trained batch; None where a size is
    missing."""
    if any(k not in config for k in _CONFIG_KEYS) \
            or any(k not in traffic for k in _TRAFFIC_KEYS):
        return None
    # the experts held here of those the router scores (the file's own
    # count where it was not cut)
    scored = config.get("published", {}).get(
        "n_routed_experts", config["n_routed_experts"])
    picks = traffic["batch_size"] * traffic["seq_len"] \
        * config["num_experts_per_tok"] * config["n_routed_experts"] \
        / float(scored)
    layers = config["num_hidden_layers"] - config["first_k_dense_replace"]
    return 3.0 * 6.0 * config["hidden_size"] \
        * config["moe_intermediate_size"] * picks * layers


def experts_ms(run):
    """Device ms a trained batch under ``moe.<key>/experts``."""
    ops = scope_groups.walk(run)
    if ops is None:
        return None
    return sum(ms for ms, path, _ in ops
               if scope_groups.outer_kind(path) == "moe"
               and scope_groups.inner_part(path).split("/")[0] == "experts")


def read(run):
    flops = useful_flops(run.config, run.traffic)
    ms = experts_ms(run)
    if flops is None or not ms:
        return None
    return 100.0 * flops / (ms / 1e3 * run.chips
                            * run.peak("bf16_flops_per_s"))
