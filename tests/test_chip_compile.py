"""Real-size compiles for the chip, without the chip.

The TPU's compiler is installed here and compiles for a chip that is
described, not attached (``v5e:2x2``). Interpret mode and the CPU
backend cannot see what it refuses: a block that does not fit VMEM, a
slice off the tiling, a program too large for HBM. So the kernels of
``layers/pallas_kernels.py`` and the layers that call them at the
cells' shapes, and AlexNet's train steps, are compiled here on every
run of the suite — a couple of seconds a kernel, ten a step. A compile
that passes is not a chip run and is never reported as one.

Only one process at a time may hold the TPU library, so the topology is
described inside a module-scoped fixture and nowhere else: never at
import, in a ``skipif``, in ``parametrize`` arguments or in conftest.
All of it stays in this one file, and every compile runs in the test's
own process with the persistent compilation cache off (an entry written
for a described chip cannot be read back without one).
"""

import os

import numpy as np
import pytest


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from cxxnet_tpu.layers import pallas_kernels
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    pallas_kernels.set_interpret(False)      # compile, as on the chip
    yield desc
    pallas_kernels.set_interpret(True)       # conftest's choice
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()


def _on(sharding):
    import jax
    return lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                          sharding=sharding)


def _described_trainer(topo, ndev, batch, extra):
    """An AlexNet NetTrainer whose mesh is ``ndev`` DESCRIBED devices.
    Nothing can be placed on them, so placement is skipped and every
    array becomes a ShapeDtypeStruct carrying the sharding the trainer
    chose; the jitted step functions are the trainer's own."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from cxxnet_tpu.models import alexnet
    from cxxnet_tpu.nnet.trainer import NetTrainer
    from cxxnet_tpu.utils.config import parse_config
    mesh = Mesh(np.array(topo.devices[:ndev]).reshape(ndev, 1),
                ("data", "model"))
    t = NetTrainer(parse_config(alexnet(nclass=1000, batch_size=batch,
                                        image_size=227))
                   + [("dtype", "bfloat16"), ("eval_train", "0"),
                      ("silent", "1")] + extra, mesh=mesh)

    def no_placement():
        t.grad_acc = jax.tree.map(jnp.zeros_like, t.params) \
            if t.update_period > 1 else None

    t._put_all = no_placement
    # the probe places an array too; the pin itself is what is compiled
    t._probe_input_layout = lambda: setattr(
        t, "input_layout_effective", t.input_layout)
    t.init_model()

    def sds(x, sharding):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    t.params = jax.tree.map(sds, t.params, t._p_shard)
    t.opt_state = jax.tree.map(sds, t.opt_state, t._o_shard)
    t.net_state = jax.tree.map(_on(t._repl), t.net_state)
    if t.grad_acc is not None:
        t.grad_acc = jax.tree.map(sds, t.grad_acc, t._p_shard)
    t._base_key = _on(t._repl)(t._base_key)
    return t


def test_alexnet_batch256_train_step_compiles_for_one_v5e(topo):
    import jax
    t = _described_trainer(topo, 1, 256, [])
    sds = jax.ShapeDtypeStruct
    u32 = sds((), np.uint32)
    compiled = t._train_step.lower(
        t.params, t.opt_state, t.net_state, None,
        sds((256, 227, 227, 3), np.float32, sharding=t._b_shard),
        sds((256, 1), np.float32, sharding=t._b_shard), None, (),
        sds((len(t._hyper_index), 3), np.float32), u32, u32,
        t._base_key, do_update=True).compile()
    mem = compiled.memory_analysis()
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert 0 < live < 16e9           # fits one v5e's HBM, with room
    # the TPU's compiler keeps the layer scopes: what a program_scopes
    # record would map of this step (the CPU backend drops some)
    from cxxnet_tpu.monitor.spans import STEP_SCOPES, scope_map
    module, scopes, fusions, mapped = scope_map(
        compiled.as_text(), t.net.scope_names + STEP_SCOPES)
    assert module == "jit_train_step"
    assert mapped >= 0.9 * fusions > 0
    paths = set(scopes.values())
    assert {"jvp(conv.conv1)", "transpose(jvp(conv.conv1))",
            "transpose(jvp(max_pooling.pool1))", "update"} <= paths, paths


def test_alexnet_up2_scanned_step_pins_the_batch_row_major(topo):
    """The alexnet_up2 bench cell: run_steps + update_period 2 +
    input_layout = rowmajor in one program, whose batch input the
    compiler must hold to the row-major layout (left alone, it picks a
    batch-minor one for this shape)."""
    t = _described_trainer(topo, 1, 128, [
        ("grad_dtype", "bfloat16"), ("momentum_dtype", "bfloat16"),
        ("update_period", "2"), ("input_layout", "rowmajor")])
    assert t.precompile(n_steps=20, per_batch=False) == 1
    (key,) = t.programs.aot
    layout = t.programs.aot[key].input_formats[0][4].layout
    assert tuple(layout.major_to_minor) == (0, 1, 2, 3)


def test_alexnet_data_parallel_step_compiles_for_four_v5e(topo):
    """chip_smoke.py --chips 4, ZeRO-1 on: the K-window step over a
    data=4 mesh compiles, reduces gradients across the chips and
    gathers the sharded update."""
    import jax
    t = _described_trainer(topo, 4, 256, [("grad_sync", "fused"),
                                          ("optim_shard", "1")])
    sds = jax.ShapeDtypeStruct
    k = 2
    compiled = t._many_step.lower(
        t.params, t.opt_state, t.net_state, None,
        sds((k, 256, 227, 227, 3), np.float32, sharding=t._kb_shard),
        sds((k, 256, 1), np.float32, sharding=t._kb_shard), None, (),
        sds((k, len(t._hyper_index), 3), np.float32),
        sds((k,), np.uint32), sds((k,), np.bool_), sds((), np.uint32),
        t._base_key, collect=False).compile()
    text = compiled.as_text()
    assert "all-reduce" in text or "reduce-scatter" in text
    assert "all-gather" in text


@pytest.mark.parametrize("kind", ["mla_attention", "moe"])
def test_decoder_layer_compiles_for_one_v5e_at_published_widths(topo, kind):
    """The two heavy sequence layers at the language-model cell's shapes
    (2 x 8,192 positions, hidden 2048, bfloat16), forward and backward.
    What the chip showed and only the chip's compiler can say (PERF.md,
    PR 28): the attention's row maximum must stay a plain reduce (XLA
    rewrote it into a ``reduce-window`` 2 x keys - 1 wide, 8,192 times the
    work) and no product may come out heads-minor (16 of 128 lanes)."""
    import re
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from cxxnet_tpu.layers import create_layer, seq_shape
    cfg = {"mla_attention": dict(
        nhead=16, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        kv_lora_rank=512, rope_theta=800000.0, eps=1e-5, q_block=1024),
        "moe": dict(nexpert=64, topk=6, nhidden=1408, nshared=2,
                    routed_scaling_factor=2.446, expert_count=8,
                    expert_block=512, bias_sigma=0.01)}[kind]
    layer = create_layer(kind, [(k, str(v)) for k, v in cfg.items()]
                         + [("dtype", "bfloat16")])
    layer.infer_shape([seq_shape(8192, 2048)])
    one = SingleDeviceSharding(topo.devices[0])

    def on(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)

    params = jax.tree.map(on, jax.eval_shape(layer.init_params,
                                             jax.random.PRNGKey(0)))
    state = jax.tree.map(on, jax.eval_shape(layer.init_state))
    x = jax.ShapeDtypeStruct((2, 8192, 2048), jnp.bfloat16, sharding=one)

    def loss(p, x):
        (y,), _ = layer.forward(p, state_v, [x], True, None)
        return jnp.sum(y.astype(jnp.float32))

    def step(p, s, x):
        nonlocal state_v
        state_v = s
        return jax.grad(loss, argnums=(0, 1))(p, x)

    state_v = None
    text = jax.jit(step).lower(params, state, x).compile().as_text()
    wide = re.findall(r"reduce-window\([^\n]*window=\{size=([0-9x]+)", text)
    assert all(max(map(int, w.split("x"))) <= 1024 for w in wide), wide
    minor = re.findall(r"= [a-z0-9]+\[[0-9,]*,(\d+)\]\{[^}]*\} convolution\(",
                       text)
    assert minor and "16" not in minor, sorted(set(minor))
    if kind == "mla_attention":
        # the causal core is the fused kernel, forward and backward, and
        # no float32 block of scores (2 x 16 heads x 1,024 queries or
        # more x 1,024 keys or more) exists outside it
        assert layer.fused_core and text.count("tpu_custom_call") >= 2
        scores = re.findall(r"f32\[(?:2,16|32),\d{4,},\d{4,}\]", text)
        assert not scores, sorted(set(scores))
    else:
        # the experts' two schedules, each the body of a loop of one
        # trip or none (layers/sequence.py: _by_budget): the grouped
        # kernels (the backward pass's three and the one that turns dx's
        # slabs back into rows) where the blocks in use fit the 80 the
        # buffers hold, and the loop a block at a time otherwise. This
        # loss needs no forward value, so of the forward pass the gather
        # of 80 blocks of rows is left alone
        assert layer.grouped and layer.budget(2 * 8192) == 80
        assert " conditional(" not in text
        assert "bf16[40960,2048]" in text
        kernels, loop = _loop_bodies(text, "(experts)")[
            "transpose(jvp(experts))"]
        # (the kernels' side holds a loop too: the cotangent's gather)
        assert kernels.count("tpu_custom_call") == 4
        assert "tpu_custom_call" not in loop and " while(" in loop


@pytest.mark.parametrize("kind", ["gqa_attention", "gated_delta_rule"])
def test_qwen3_next_mixers_compile_for_one_v5e_at_the_cells_shapes(topo, kind):
    """The two mixers of ``qwen3_next.train_tokens_8k`` (2 x 8,192
    positions, bfloat16), forward and backward. Gated attention: 16 query
    heads on 2 key/value heads of 256 features, 64 of them rotated: the
    fused kernel takes it (a head width it had not seen: 16.8 MB of
    ``dQ``, inside its gate) and no float32 block of scores exists
    outside it. The delta rule in chunks of 64: the scan is the two
    fused kernels (layers/pallas_kernels.py: gated_delta_scan) and no
    loop, the float32 matrices a chunk a head (128 chunks x 2 x 16 key
    or 32 value heads x 64 x 64) that are left outside them all belong
    to the triangular inverse and what builds its argument (scope
    ``solve``), the running sums are windows of a chunk and no wider,
    and what the backward pass holds stays under 3 GB (1.1; the XLA
    form held 2.3)."""
    import re
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from cxxnet_tpu.layers import create_layer, seq_shape
    from cxxnet_tpu.layers.sequence import gated_delta_rule
    one = SingleDeviceSharding(topo.devices[0])
    bf, f32 = jnp.bfloat16, jnp.float32

    def on(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    if kind == "gqa_attention":
        layer = create_layer(kind, [(k, str(v)) for k, v in dict(
            nhead=16, nkvhead=2, head_dim=256, window=0, rope=1, rope_dim=64,
            rope_theta=1e7, eps=1e-6, q_block=1024, dtype="bfloat16").items()])
        layer.infer_shape([seq_shape(8192, 2048)])
        params = jax.tree.map(lambda a: on(a.shape, a.dtype), jax.eval_shape(
            layer.init_params, jax.random.PRNGKey(0)))
        text = jax.jit(jax.grad(lambda p, x: jnp.sum(layer.forward(
            p, {}, [x], True, None)[0][0].astype(f32)), argnums=(0, 1))).lower(
                params, on((2, 8192, 2048), bf)).compile().as_text()
        assert layer.fused_core and text.count("tpu_custom_call") >= 2
        scores = re.findall(r"f32\[(?:2,16|32),\d{4,},\d{4,}\]", text)
        assert not scores, sorted(set(scores))
        return
    args = (on((2, 8192, 16, 128), bf), on((2, 8192, 16, 128), bf),
            on((2, 8192, 32, 128), bf), on((2, 8192, 32), f32),
            on((2, 8192, 32), f32))
    compiled = jax.jit(jax.grad(lambda *a: jnp.sum(gated_delta_rule(
        *a, 64, bf).astype(f32)), argnums=(0, 1, 2, 3, 4))).lower(
            *args).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2 and " while(" not in text
    # (an instruction's scope is in its metadata; parameters and the
    # bitcasts inside fusions carry none)
    chunk = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in text.splitlines() if "op_name=" in line
             and re.search(r"= f32\[2,16,(?:2,)?128,64,64\]", line)]
    assert chunk and all("solve" in name for name in chunk), chunk
    wide = re.findall(r"reduce-window\([^\n]*window=\{size=([0-9x]+)", text)
    assert wide and all(max(map(int, w.split("x"))) <= 64 for w in wide), wide
    assert compiled.memory_analysis().temp_size_in_bytes < 3e9


@pytest.mark.parametrize("kind", ["kernels", "layer"])
def test_gated_delta_conv_compiles_for_one_v5e_at_the_cells_shapes(topo, kind):
    """The short convolution of ``qwen3_next.train_tokens_8k``'s
    linear-attention layers (2 x 8,192 positions, 16 key heads and 32
    value heads of 128, four taps, bfloat16) as the fused kernels
    (layers/pallas_kernels.py: gated_delta_conv): alone, a forward and a
    backward custom call and the gradients in the operands' shapes. The
    whole mixer's gradient under a ``remat = block`` checkpoint: the
    convolution's forward kernel twice and backward once, the scan's
    kernels once each; no float32 relayout of q or k and no copy of
    ``qkv`` (the XLA form made them between its head-tiled arrays and
    the scan's blocks: 2.71 GB of copies a layer, now 0.89), and what
    the backward pass holds under 2.5 GB (2.03; the XLA form 2.70)."""
    import re
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from cxxnet_tpu.layers import create_layer, seq_shape
    from cxxnet_tpu.layers import pallas_kernels as pk
    from cxxnet_tpu.layers.base import BLOCK_REMAT_KEEPS
    one = SingleDeviceSharding(topo.devices[0])
    bf, f32 = jnp.bfloat16, jnp.float32

    def on(shape, dtype=bf):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    if kind == "kernels":
        assert pk.gated_delta_conv_applicable(8192, 4, 16, 32, 128, 128, bf)
        args = (on((2, 8192, 8192)), on((4, 8192), f32))
        step = jax.jit(jax.value_and_grad(lambda qkv, taps: sum(
            jnp.sum(o.astype(f32)) for o in pk.gated_delta_conv(
                qkv, taps, 2048, 128)), argnums=(0, 1)))
        compiled = step.lower(*args).compile()
        assert compiled.as_text().count("tpu_custom_call") == 2
        assert [x.shape for x in compiled.out_info[1]] == [
            a.shape for a in args]
        return
    layer = create_layer("gated_delta", [(k, str(v)) for k, v in dict(
        nkhead=16, nvhead=32, key_dim=128, value_dim=128, conv_kernel=4,
        chunk=64, eps=1e-6, dtype="bfloat16").items()])
    layer.infer_shape([seq_shape(8192, 2048)])
    assert layer.fused_scan and layer.fused_conv
    params = jax.tree.map(lambda a: on(a.shape, a.dtype), jax.eval_shape(
        layer.init_params, jax.random.PRNGKey(0)))
    seg = jax.checkpoint(
        lambda p, x: layer.forward(p, {}, [x], True, None)[0][0],
        policy=jax.checkpoint_policies.save_only_these_names(
            *BLOCK_REMAT_KEEPS))
    compiled = jax.jit(jax.grad(lambda p, x: jnp.sum(seg(p, x).astype(
        f32)), argnums=(0, 1))).lower(params, on((2, 8192, 2048))).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 5
    copies = re.findall(r"= (\w+)\[([0-9,]+)\]\{[^}]*\} copy\(", text)
    assert not [c for c in copies if c[1] == "2,8192,8192" or (
        c[0] == "f32" and c[1] == "2048,8,16,128")], copies
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5e9


def _loop_bodies(text, scope):
    """``{scope path: [text reachable from the body of each loop]}`` of
    the outermost ``while`` instructions of an HLO module whose op_name
    holds ``scope``, in program order."""
    import re
    comps = {m.group(1): m.group(2) for m in re.finditer(
        r"^(?:ENTRY )?%?([\w.\-]+) \([^\n]*\{\n(.*?)^\}", text,
        re.M | re.S)}

    def reach(name, seen):
        if name in seen or name not in comps:
            return ""
        seen.add(name)
        body = comps[name]
        called = re.findall(
            r"(?:calls|body|condition|to_apply)=%?([\w.\-]+)", body)
        return body + "".join(reach(c, seen) for c in called)

    out = {}
    for m in re.finditer(r" while\([^\n]*body=%?([\w.\-]+)[^\n]*"
                         r"op_name=\"([^\"]*)\"", text):
        path = m.group(2).split("/")
        if scope in m.group(2) and path[-1] == "while" \
                and "while" not in path[:-1]:
            out.setdefault("/".join(p for p in path if scope in p), []) \
                .append(reach(m.group(1), set()))
    return out


def test_grouped_expert_kernels_compile_for_one_v5e_at_the_cells_shapes(topo):
    """The routed experts' kernels alone at the language-model cell's
    shapes (buffers of 80 blocks of 512 rows = 40,960 rows of 2,048, 8
    experts of 2,048 x 1,408, bfloat16, a traced count of blocks in use
    as the grid): Mosaic takes the traced grid, an expert's three whole
    weight matrices twice over beside the float32 rows of the backward
    kernel (over 70 MB of the 96 MB of VMEM the kernels ask for; 64 MB
    does not hold it), a copy a row between a token's slab of the sums
    in HBM and VMEM, and the weight gradients' transposed products with
    their float32 sums in scratch."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from cxxnet_tpu.layers import pallas_kernels as pk
    one = SingleDeviceSharding(topo.devices[0])
    held, d, w, block, rows = 8, 2048, 1408, 512, 80 * 512
    assert pk.grouped_experts_applicable(d, w, block, jnp.bfloat16)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    xs, g, cw = sds((rows, d)), sds((rows, d)), sds((rows, 1), jnp.float32)
    ws = (sds((held, d, w)), sds((held, d, w)), sds((held, w, d)))
    expert, nb = sds((200,), jnp.int32), sds((), jnp.int32)
    tok, tokens = sds((rows,), jnp.int32), 2 * 8192
    fwd = jax.jit(lambda xs, cw, tok, *a: pk.experts_forward(
        xs, cw, tok, *a, block, tokens)).lower(
            xs, cw, tok, *ws, expert, nb).compile()
    # the experts' kernel, and the one that turns the sums' slabs to rows
    assert fwd.as_text().count("tpu_custom_call") == 2
    assert (fwd.out_info.shape, fwd.out_info.dtype) \
        == ((tokens, d), jnp.float32)
    bwd = jax.jit(lambda xs, g, cw, tok, *a: pk.experts_backward(
        xs, g, cw, tok, *a, block, tokens)).lower(
            xs, g, cw, tok, *ws, expert, nb).compile()
    assert bwd.as_text().count("tpu_custom_call") == 4
    dx, dwg, dwu, dwd, dcw = bwd.out_info
    assert (dx.shape, dx.dtype) == ((tokens, d), jnp.bfloat16)
    assert [(o.shape, o.dtype) for o in (dwg, dwu, dwd)] \
        == [(a.shape, a.dtype) for a in ws]
    assert dcw.shape == (rows, 1)


def test_causal_attention_compiles_for_one_v5e_at_the_cells_shapes(topo):
    """The fused causal attention alone, forward and backward, at the
    language-model cell's shapes (2 x 16 heads x 8,192 positions, queries
    and keys of 128 + 64 with one shared k_rope, values of 128, q_block
    1,024, bfloat16): Mosaic takes the 64-wide part, the transposed
    product of the backward pass and the 64 MB of VMEM the kernels ask
    for (the float32 dQ of a sequence stays there)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from cxxnet_tpu.layers import pallas_kernels as pk
    one = SingleDeviceSharding(topo.devices[0])
    b, h, t = 2, 16, 8192
    assert pk.causal_attention_applicable(t, 1024, (128, 64), 128)

    def sds(heads, d):
        return jax.ShapeDtypeStruct((b, heads, t, d), jnp.bfloat16,
                                    sharding=one)

    def loss(qn, qr, kn, kr, v):
        o = pk.causal_attention((qn, qr), (kn, kr), v, 192 ** -0.5, 1024)
        return jnp.sum(o.astype(jnp.float32))

    args = (sds(h, 128), sds(h, 64), sds(h, 128), sds(1, 64), sds(h, 128))
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))) \
        .lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2
    grads = compiled.out_info
    assert [g.shape for g in grads] == [a.shape for a in args]


@pytest.mark.parametrize("window", [2048, 0])
def test_grouped_window_attention_compiles_at_trinitys_shapes(topo, window):
    """The same kernel as ``gqa_attention`` calls it in
    ``trinity_mini.train_tokens_8k``: 2 x 32 query heads on 4 key/value
    heads x 8,192 positions of 128 features, q_block 1,024, bfloat16, a
    sliding layer's window of 2,048 keys and a full layer's none. Mosaic
    takes the index maps that start at the band's first tile and the
    grids whose inner axes are 3 tiles long where the full layer's are 8."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from cxxnet_tpu.layers import pallas_kernels as pk
    one = SingleDeviceSharding(topo.devices[0])
    b, h, g, t, d = 2, 32, 4, 8192, 128
    assert pk.causal_attention_applicable(t, 1024, (d,), d, h, g, window)
    assert pk._band_tiles(t, 1024, 1024, window) == ((3, 3) if window
                                                     else (8, 8))

    def sds(heads):
        return jax.ShapeDtypeStruct((b, heads, t, d), jnp.bfloat16,
                                    sharding=one)

    def loss(q, k, v):
        o = pk.causal_attention((q,), (k,), v, d ** -0.5, 1024, window)
        return jnp.sum(o.astype(jnp.float32))

    args = (sds(h), sds(g), sds(g))
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))) \
        .lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2
    assert [x.shape for x in compiled.out_info] == [a.shape for a in args]


@pytest.mark.parametrize("kind", ["gated_conv", "gqa_attention", "core",
                                  "moe"])
def test_lfm2_layers_compile_for_one_v5e_at_the_cells_shapes(topo, kind):
    """What ``lfm2_24b_a2b.train_tokens_8k`` adds (2 x 8,192 positions,
    hidden 2,048, bfloat16), forward and backward. The short-convolution
    mixer: no XLA convolution over time is left (the three shifted
    products fuse; as a depthwise convolution the pass took nine times
    the projections beside it, PERF.md, PR 34) and what the backward
    pass holds stays under 1 GB (0.54). The attention layer, 32 query heads on
    8 key/value heads of 64 features, no gate: the fused kernel takes
    the 64-wide values as they are and no float32 block of scores exists
    outside it. The kernel alone at those heads: two custom calls, the
    gradients in the operands' shapes. The expert layer with no shared
    expert at width 1,536: the grouped kernels fit their 96 MiB and no
    ``shared`` scope is opened."""
    import re
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from cxxnet_tpu.layers import create_layer, seq_shape
    from cxxnet_tpu.layers import pallas_kernels as pk
    one = SingleDeviceSharding(topo.devices[0])
    bf, f32 = jnp.bfloat16, jnp.float32

    def on(shape, dtype=bf):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    if kind == "core":
        b, h, g, t, d = 2, 32, 8, 8192, 64
        assert pk.causal_attention_applicable(t, 1024, (d,), d, h, g)
        args = (on((b, h, t, d)), on((b, g, t, d)), on((b, g, t, d)))
        compiled = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
            pk.causal_attention((q,), (k,), v, d ** -0.5, 1024).astype(f32)),
            argnums=(0, 1, 2))).lower(*args).compile()
        assert compiled.as_text().count("tpu_custom_call") == 2
        assert [x.shape for x in compiled.out_info] == [a.shape for a in args]
        return
    cfg = {"gated_conv": dict(conv_kernel=3),
           "gqa_attention": dict(nhead=32, nkvhead=8, head_dim=64, window=0,
                                 rope=1, gate=0, rope_theta=1e6, eps=1e-5,
                                 q_block=1024),
           "moe": dict(nexpert=64, topk=4, nhidden=1536, nshared=0,
                       routed_scaling_factor=1, expert_count=8,
                       expert_block=512, bias_sigma=0.01)}[kind]
    layer = create_layer(kind, [(k, str(v)) for k, v in cfg.items()]
                         + [("dtype", "bfloat16")])
    layer.infer_shape([seq_shape(8192, 2048)])
    params = jax.tree.map(lambda a: on(a.shape, a.dtype), jax.eval_shape(
        layer.init_params, jax.random.PRNGKey(0)))
    state = jax.tree.map(lambda a: on(a.shape, a.dtype),
                         jax.eval_shape(layer.init_state))
    compiled = jax.jit(jax.grad(lambda p, s, x: jnp.sum(layer.forward(
        p, s, [x], True, None)[0][0].astype(f32)), argnums=(0, 2))).lower(
            params, state, on((2, 8192, 2048))).compile()
    text = compiled.as_text()
    if kind == "gated_conv":
        # the projections are the only convolutions XLA sees
        convs = [re.search(r'op_name="([^"]*)"', line).group(1)
                 for line in text.splitlines() if " convolution(" in line]
        assert len(convs) == 5 and all(
            "in_proj" in n or "out_proj" in n for n in convs), convs
        assert "feature_group_count" not in text
        assert compiled.memory_analysis().temp_size_in_bytes < 1e9
    elif kind == "gqa_attention":
        assert layer.fused_core and "wg" not in params
        assert text.count("tpu_custom_call") >= 2
        scores = re.findall(r"f32\[(?:2,32|64),\d{4,},\d{4,}\]", text)
        assert not scores, sorted(set(scores))
    else:
        assert layer.grouped and layer.budget(2 * 8192) == 56
        assert "tpu_custom_call" in text and "(shared)" not in text
        assert " conditional(" not in text


@pytest.mark.parametrize("kind", ["moe", "gqa_attention"])
def test_mellum2_layers_compile_for_four_v5e_at_the_cells_shapes(topo, kind):
    """What ``mellum2_12b_a2_5b.train_tokens_8k_ep4`` adds (8 x 8,192
    positions, two sequences a chip, hidden 2,304, bfloat16), forward and
    backward over a described v5e:2x2. The expert layer on an expert axis
    of four: its 64 experts 16 a chip, a chip's 16,384 tokens exchanged
    in parts of 4,096, each token's row gathered once to every chip (an
    all-gather of 16,384 rows) and the partial sums returned by an
    all-to-all of four blocks of 4,096, no block of a row a pick (32,768
    rows) on any collective; the grouped kernels at hidden 2,304 and
    width 896 (their rows' conversion back from the float32 slabs in
    blocks that fit the default scoped VMEM, which 512 rows at 2,304 do
    not), no all-reduce or gather of an expert's tensor, and temporaries
    well under the 5.28 GiB that blocks of a row a pick held. The full
    attention layer
    with YaRN's tables: the fused core a chip's rows at a time inside
    ``shard_map`` (the partitioner cannot split a Mosaic kernel)."""
    import re
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from cxxnet_tpu.layers import create_layer, seq_shape
    mesh = Mesh(np.array(topo.devices[:4]).reshape(4, 1), ("data", "model"))
    bf, f32 = jnp.bfloat16, jnp.float32
    cfg = {"moe": dict(nexpert=64, topk=8, nhidden=896, nshared=0,
                       score_func="softmax", expert_block=512,
                       expert_axis="data"),
           "gqa_attention": dict(nhead=32, nkvhead=4, head_dim=128, window=0,
                                 rope=1, gate=0, rope_theta=5e5, eps=1e-6,
                                 q_block=1024, rope_type="yarn",
                                 rope_factor=16,
                                 original_max_position_embeddings=8192,
                                 beta_fast=32, beta_slow=1,
                                 attention_factor=1.2772588722239782)}[kind]
    layer = create_layer(kind, [(k, str(v)) for k, v in cfg.items()]
                         + [("dtype", "bfloat16")])
    layer.infer_shape([seq_shape(8192, 2304)])
    layer.bind_mesh(mesh)
    lead = getattr(layer, "leading_axes", dict)()

    def on(a, spec=P()):
        return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                    sharding=NamedSharding(mesh, spec))

    shapes = jax.eval_shape(layer.init_params, jax.random.PRNGKey(0))
    params = {tag: on(a, P(lead[tag]) if tag in lead else P())
              for tag, a in shapes.items()}
    state = jax.tree.map(on, jax.eval_shape(layer.init_state))
    x = on(jax.ShapeDtypeStruct((8, 8192, 2304), bf), P("data"))
    compiled = jax.jit(jax.grad(lambda p, s, x: jnp.sum(layer.forward(
        p, s, [x], True, None)[0][0].astype(f32)), argnums=(0, 2))).lower(
            params, state, x).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    if kind == "moe":
        assert layer.grouped and layer.chips() == 4
        assert layer.part(16384) == 4096 and layer.capacity(16384) == 16384
        collectives = [ln for ln in text.splitlines() if re.search(
            r"(all-to-all|all-gather|all-reduce|reduce-scatter|"
            r"collective-permute)(-start)?\(", ln)]
        assert not any("32768" in ln for ln in collectives)
        assert any(re.search(r"= bf16\[16384,2304\]\S* all-gather(-start)?\(",
                             ln) for ln in collectives)
        assert any(re.search(r"= bf16\[4,4096,2304\]\S* all-to-all\(", ln)
                   for ln in collectives)
        assert not any(re.search(r"\[(64|16),(2304,896|896,2304)\]", ln)
                       and re.search(r"all-(reduce|gather)(-start)?\(", ln)
                       for ln in collectives)
        assert compiled.memory_analysis().temp_size_in_bytes < 4.5 * 2 ** 30
        assert " conditional(" not in text
    else:
        assert layer.fused_core and layer.yarn() is not None
        assert text.count("tpu_custom_call") == 2
        assert not re.search(r"all-(reduce|gather)(-start)?\(.*f32\[4,32",
                             text)
