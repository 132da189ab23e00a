"""The training driver for a language model of lfm2_moe's keys (LiquidAI's
LFM2): ``drivers/train_tokens.py`` as it is, with the one function that
names a family's configuration keys replaced.

``train_tokens.py`` builds the net from the configuration's netconfig,
makes the seeded batch, runs the plain reference and the comparison that
decides ``correct``, and times the window; none of that knows the model.
Its ``reference_config`` lists the keys of DeepSeek-V3's family
(``kv_lora_rank``, ``first_k_dense_replace``, ...), which an lfm2_moe
configuration does not have. This file loads that driver as a module,
sets its ``reference_config`` to the one below and hands ``run`` on: the
lookup is by the module's global name at call time, so ``run_reference``
takes this one (``drivers/train_tokens_afmoe.py`` and
``drivers/train_tokens_qwen3_next.py`` do the same for their families'
keys). Nothing is copied.
"""

import importlib.util
import os
from typing import Any, Dict, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


tokens = _load(os.path.join(HERE, "train_tokens.py"),
               "bench_driver_train_tokens")


def reference_config(c: Dict[str, Any]) -> Tuple[Dict[str, Any], Tuple]:
    """The reference's sizes from the configuration file: the published
    keys as they stand, ``layer_types`` cut to the published layers this
    chip holds (``layers_held``, indices into the published list), the
    router at its published width, and the experts held here as
    ``(first, count)``."""
    cfg = {k: c[k] for k in (
        "hidden_size", "num_hidden_layers", "num_dense_layers",
        "num_attention_heads", "num_key_value_heads", "norm_eps",
        "conv_L_cache", "intermediate_size", "moe_intermediate_size",
        "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
        "use_expert_bias", "vocab_size")}
    cfg["layer_types"] = tuple(c["layer_types"][i] for i in c["layers_held"])
    cfg["rope_theta"] = float(c["rope_parameters"]["rope_theta"])
    cfg["num_experts"] = int(c["published"]["num_experts"])
    return cfg, (int(c["expert_first"]), int(c["num_experts"]))


tokens.reference_config = reference_config
run = tokens.run
