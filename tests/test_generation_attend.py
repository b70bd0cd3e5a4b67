"""``generation._attend`` scores grouped query heads against K and V as the
cache holds them. First against a reference written here with the explicit
``jnp.repeat`` it replaced, which pins the head mapping j -> j // G; then in
the serving cells' programs, compiled for a described v5e chip: no array of
the repeated shape is left, and no slice-sized copy stands in its place. The
same compiles show that a decode step writes the cache in place: it holds no
second copy of the cache and puts no layer's slice back into a stack; and that
Mixtral's programs read a layer's experts where the stacked parameter holds
them: nothing of an expert tensor's size is copied, gathered or broadcast.
And that a decode step's attention is the kernel of ``ops/decode_attention.py``
over the whole cache stack: the Mosaic call is in every cell's decode program,
no layer's K or V slice and no array of logits over ``T_max`` is; the prefill
programs hold no such call and have not changed by a byte.
The four programs of the two one-pass cells are held to their lowered text,
and the looped cell's programs to one cache, one layer body and no copy
of the cache. Every program is compiled over the weights in the layout the engine
installs, q, k and v as one kernel a stack, and none copies or stages a projection
weight before its dot.

The topology is described in a fixture (never while a module is imported);
``tests/chipbench/test_chipbench_aot.py`` is the other file that does so."""

import hashlib
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from accelerate_tpu.generation import _attend, dequantize_kv_page, quantize_kv_page


def _attend_with_repeat(q, k, v, q_positions, kv_valid=None):
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    seen = jnp.arange(k.shape[1])[None, None, :] <= q_positions[:, :, None]
    if kv_valid is not None:
        seen = seen & kv_valid[:, None, :]
    logits = jnp.where(seen[:, None], logits, jnp.finfo(logits.dtype).min)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, axis=-1), v)


@pytest.mark.parametrize("masked", [False, True], ids=["all_rows", "kv_valid"])
@pytest.mark.parametrize("quantized", [False, True], ids=["float", "quant_pages"])
@pytest.mark.parametrize("sq", [1, 5])
@pytest.mark.parametrize("hq,hkv", [(8, 8), (8, 2), (8, 1)], ids=["mha", "gqa", "mqa"])
def test_attend_equals_the_explicit_repeat(hq, hkv, sq, quantized, masked):
    b, t, d = 3, 16, 8
    kq, kk, kv = jax.random.split(jax.random.key(hq * 100 + hkv * 10 + sq), 3)
    q = jax.random.normal(kq, (b, sq, hq, d), jnp.float32)
    k = jax.random.normal(kk, (b, t, hkv, d), jnp.float32)
    v = jax.random.normal(kv, (b, t, hkv, d), jnp.float32)
    # each row at its own offset, as the slot cache has them
    q_positions = jnp.array([3, 7, 10])[:, None] + jnp.arange(sq)[None, :]
    kv_valid = (jnp.arange(t)[None, :] >= jnp.array([0, 2, 5])[:, None]) if masked else None
    cached_k, cached_v = (quantize_kv_page(k), quantize_kv_page(v)) if quantized else (k, v)
    if quantized:
        k, v = dequantize_kv_page(cached_k, q.dtype), dequantize_kv_page(cached_v, q.dtype)

    out = _attend(q, cached_k, cached_v, q_positions, kv_valid)

    assert out.shape == (b, sq, hq, d) and out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_attend_with_repeat(q, k, v, q_positions, kv_valid)),
                               rtol=2e-5, atol=2e-5)


# -- the compiled programs of the serving cells ------------------------------------


# A Mosaic kernel rides in the lowered text as its serialized module, which names
# its source file by absolute path and every operation by line: left out of the
# hashes below, so that they say the same in any checkout (the kernel is held by
# ``tests/test_decode_attention.py``; a text with no kernel in it is hashed whole).
_KERNEL_BODY = re.compile(r'(\\22body\\22: \\22)[A-Za-z0-9+/=]+')


@pytest.fixture(scope="module")
def cell_programs():
    """``get(cell_name) -> (cell, {"decode": compiled, "prefill": compiled})``, each
    cell's programs as its engine runs them (:func:`_engine_program`), compiled once
    for a described v5e chip; ``get.lowered[cell_name]`` holds the sha256 of each
    program's lowered StableHLO text, and ``get.fused(cell_name)`` the decode step
    a chunk rides."""
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    from chipbench import spec

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # such a compile is written to the persistent cache and cannot be read back
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    compiled, fused = {}, {}

    def get(name):
        if name not in compiled:
            cell = spec.load_cell(name)
            lowered = {program: _engine_program(cell, topo.devices[0], program)
                       for program in ("decode", "prefill")}
            get.lowered[name] = {program: hashlib.sha256(
                _KERNEL_BODY.sub(r"\1", low.as_text()).encode()).hexdigest()
                for program, low in lowered.items()}
            compiled[name] = cell, {program: low.compile() for program, low in lowered.items()}
        return compiled[name]

    def get_fused(name):
        if name not in fused:
            fused[name] = _engine_program(get(name)[0], topo.devices[0], "decode_chunk").compile()
        return fused[name]

    get.lowered = {}
    get.fused = get_fused
    yield get
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _engine_program(cell, device, program):
    """One of the cell's engine programs, lowered for ``device`` from shapes alone:
    ``decode`` over every slot, ``prefill`` at the largest rung, or ``decode_chunk``,
    the decode step that rung rides. ``chipbench.aot.serving_programs`` lowers the
    first two over the weights in the model's layout; here they are in the layout
    ``ServingEngine`` installs (``generation.fuse_qkv_params``, here over shapes)."""
    from jax.sharding import SingleDeviceSharding

    from accelerate_tpu import serving
    from accelerate_tpu.generation import GENERATION_PLANS, _fuse_qkv, _qkv_layout, init_slot_cache
    from chipbench import weights

    place = SingleDeviceSharding(device)

    def shape(s, dtype):
        return jax.ShapeDtypeStruct(tuple(s), dtype, sharding=place)

    def abstract(fn, *args):
        return jax.tree.map(lambda x: shape(x.shape, x.dtype), jax.eval_shape(fn, *args))

    eng = cell.workload["engine"]
    n_slots, max_len = int(eng["n_slots"]), int(eng["max_len"])
    module = cell.family.program_module(cell.config, max_len)
    params = abstract(lambda tree: _qkv_layout(tree, _fuse_qkv)[0], weights.nest(
        {k: shape(s, jnp.bfloat16) for k, (s, _) in cell.family.weight_specs(cell.config).items()}))
    fwd, cfg = GENERATION_PLANS[type(module).__name__], module.config
    sampling = (0.0, None, None, None)   # greedy, no EOS: as drivers/serve.py builds the engine
    cache = abstract(lambda: init_slot_cache(cfg, n_slots, max_len, dtype=jnp.bfloat16))
    state = abstract(lambda: serving.init_slot_state(n_slots, seed=0, history=16))
    live = shape((n_slots,), jnp.bool_)
    chunk = shape((1, max(serving.default_prefill_ladder(max_len))), jnp.int32)
    scalar, flag = shape((), jnp.int32), shape((), jnp.bool_)
    key = abstract(lambda: jax.random.key(0))
    if program == "decode":
        return serving._build_decode_step(fwd, cfg, *sampling, speculate_k=0).lower(
            params, cache, state, live)
    if program == "prefill":
        return serving._build_prefill_step(fwd, cfg, *sampling).lower(
            params, cache, state, chunk, scalar, scalar, scalar, key, flag, flag)
    return serving._build_decode_chunk_step(fwd, cfg, *sampling).lower(
        params, cache, state, live, chunk, scalar, scalar, scalar, key, flag, flag)


_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%(\S+) = \w+\[([0-9,]*)\](\S*) ([\w\-]+)\(")


def _instructions(hlo_text):
    """(dims, opcode, name, layout, inside a fusion) of every array-valued
    instruction; the layout (``{...}``) holds the tiling and the memory space."""
    inside_fusion = False
    for line in hlo_text.splitlines():
        if line.startswith(("%", "ENTRY")):
            inside_fusion = line.startswith("%fused_computation")
        m = _INSTRUCTION.match(line)
        if m:
            dims = tuple(int(n) for n in m.group(2).split(",") if n)
            yield dims, m.group(4), m.group(1), m.group(3), inside_fusion


def _arrays(hlo_text, scheduled_only=False):
    """(elements, opcode, name) of every array-valued instruction; with
    ``scheduled_only`` those a fusion holds inside itself are left out, since
    they never reach memory as a buffer of their own."""
    for dims, op, name, _, inside_fusion in _instructions(hlo_text):
        if not (scheduled_only and inside_fusion):
            yield math.prod(dims), op, name


@pytest.mark.parametrize("case", ["decode_no_repeated_array", "prefill_no_repeated_array",
                                  "decode_no_slice_sized_copy"])
def test_the_steady_cell_compiles_without_the_gqa_repeat(cell_programs, case):
    cell, programs = cell_programs("mistral_serve_steady")
    cfg, eng = cell.config, cell.workload["engine"]
    program = programs[case.split("_")[0]]
    heads, kv_heads, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    rows = (eng["n_slots"] if program is programs["decode"] else 1) * eng["max_len"]
    kv_slice, repeated = rows * kv_heads * d, rows * heads * d
    if case == "decode_no_repeated_array":
        # K or V copied out once for every query head, in whatever shape
        assert repeated not in {n for n, _, _ in _arrays(program.as_text())}
    elif case == "prefill_no_repeated_array":
        # one slot's rows: the logits over the vocabulary and a four-layer slice
        # of the cache have as many elements, so here only what a broadcast writes
        assert repeated not in {n for n, op, _ in _arrays(program.as_text()) if op == "broadcast"}
    else:
        moved = [op for n, op, _ in _arrays(program.as_text(), scheduled_only=True)
                 if n == kv_slice and op in ("copy", "transpose")]
        assert not moved


# One layer's K and V slices in bf16 for the steady cell: the cache rides the layer
# loop's carry and is written in place, so beside the donated cache a decode step
# holds less than that. Mixtral's step contracts over the stacked experts in place
# (135.5 MB when this was written): a quarter of one of a layer's three expert
# tensors in bf16, where a single copy of one would be the whole of it.
@pytest.mark.parametrize("cell_name,temporaries_under", [
    ("mistral_serve_steady", 2 * 2 * 24 * 2048 * 8 * 128),
    ("mixtral_serve_decode", 2 * 8 * 4096 * 14336 // 4),
], ids=["steady", "mixtral"])
@pytest.mark.parametrize("case", ["temporaries", "no_cache_sized_copy_or_put_back"])
def test_the_decode_program_writes_the_cache_in_place(cell_programs, cell_name,
                                                      temporaries_under, case):
    from chipbench import aot

    cell, programs = cell_programs(cell_name)
    cfg, eng = cell.config, cell.workload["engine"]
    cache = (cfg["num_hidden_layers"] * eng["n_slots"] * eng["max_len"]
             * cfg["num_key_value_heads"] * cfg["head_dim"])
    if case == "temporaries":
        assert aot.memory_of(programs["decode"])["temporaries"] < temporaries_under
    else:
        # K's or V's whole stack copied, or a fusion that puts a layer's slice into
        # a stack (XLA names a fusion after what it holds); the scatter fusion that
        # writes the new rows has the cache's shape too, and aliases its operand
        moved = [name for n, op, name in _arrays(programs["decode"].as_text(), scheduled_only=True)
                 if n == cache and (op == "copy" or "dynamic-update-slice" in name)]
        assert not moved


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_mixtral_reads_a_layers_experts_where_they_lie(cell_programs, program):
    cell, programs = cell_programs("mixtral_serve_decode")
    cfg = cell.config
    experts = cfg["num_local_experts"] * cfg["hidden_size"] * cfg["intermediate_size"]
    # w_gate[e] under a vmap was a gather that XLA spelled as a loop of
    # dynamic-slice -> dynamic-update-slice into a zero broadcast, all of this size
    moving = ("copy", "gather", "broadcast", "dynamic-slice", "dynamic-update-slice")
    moved = [name for n, op, name in _arrays(programs[program].as_text(), scheduled_only=True)
             if n == experts and any(word in op or word in name for word in moving)]
    assert not moved


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_the_steady_cell_runs_no_expert_code(cell_programs, program):
    # the scope ``moe.experts`` and the parameter ``...moe__w_gate`` name it in Mixtral's text
    assert "moe" in cell_programs("mixtral_serve_decode")[1][program].as_text()
    assert "moe" not in cell_programs("mistral_serve_steady")[1][program].as_text()


# -- decode attention: the kernel over the stack, chosen by what the program is lowered for


@pytest.mark.parametrize("case", ["decode_holds_the_kernel_once", "no_layer_slice_of_the_cache",
                                  "no_logits_over_t_max", "prefill_holds_no_kernel"])
@pytest.mark.parametrize("cell_name", ["mistral_serve_steady", "mixtral_serve_decode",
                                       "ouro_serve_reason"])
def test_a_decode_step_reads_the_cache_through_the_kernel(cell_programs, cell_name, case):
    """These compiles run in a process that holds a CPU: the choice between the
    kernel and the dots over a slice is made for the platform the program is
    lowered for, so the text here is the chip's program."""
    cell, programs = cell_programs(cell_name)
    cfg, eng = cell.config, cell.workload["engine"]
    b, t = eng["n_slots"], eng["max_len"]
    hq, hkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    decode = programs["decode"].as_text()
    kernels = [line for line in decode.splitlines()
               if "custom-call(" in line and "tpu_custom_call" in line]
    if case == "decode_holds_the_kernel_once":
        # one call in the one layer body, named for the trace to find it
        assert len(kernels) == 1 and "%decode_attention" in kernels[0]
        # handed both whole stacks, not a slice of them
        planes = cfg.get("total_ut_steps", 1) * cfg["num_hidden_layers"]
        assert kernels[0].count(f"bf16[{planes},{b},{t},{hkv},{d}]") >= 2
    elif case == "no_layer_slice_of_the_cache":
        # the lift-out of a layer's K or V (PERF.md, section 5: 4.3 of a 17.3 ms step)
        assert f"[1,{b},{t},{hkv},{d}]" not in decode and f"bf16[{b},{t},{hkv},{d}]" not in decode
    elif case == "no_logits_over_t_max":
        # _attend's scores and weights over every row of every slot, as XLA shaped them
        shapes = {f"[{b},{hkv},{hq // hkv},{t}]", f"[{b},{hkv},{hq // hkv},1,{t}]",
                  f"[{b},{t},{hq}]", f"[{b},{hq},{t}]"}
        assert not [shape for shape in shapes if shape in decode]
    else:
        assert "tpu_custom_call" not in programs["prefill"].as_text()


# -- a stack run more than once: the programs that were there stay, to the byte ------

# sha256 of the lowered StableHLO text (no locations in it) of the four programs. With
# one pass the traced program is the one the layer loop traced before it learned to run
# a stack more than once. A PR that means to change one of these programs replaces its
# line, and says which; one that does not has touched their path. All four were taken
# anew when the engine came to install q, k and v as one ``qkv_proj`` kernel a stack:
# one dot a layer, its output cut in three, where three contractions over ``(H, n, D)``
# kernels stood.
LOWERED_BEFORE_PASSES = {
    ("mistral_serve_steady", "decode"):
        "a104d746590c073766910130dddeecff0cbe058bf75a2ed1cf645a0e61e68a66",
    ("mistral_serve_steady", "prefill"):
        "4941305375d73451fded0f7586ace3984758bccf7f1cefb53b53d0fffaa582da",
    ("mixtral_serve_decode", "decode"):
        "68682633519de7c9f43f5f15d258b7b10607e69322636fb1fc1fb42881ed1508",
    ("mixtral_serve_decode", "prefill"):
        "745b56ef0f8b8de5c8cab6ffe2a20a60c1e27b2ff15c05094b4cfab116e1181f",
}


@pytest.mark.parametrize("cell_name,program", sorted(LOWERED_BEFORE_PASSES))
def test_the_one_pass_cells_lower_to_the_programs_they_were(cell_programs, cell_name, program):
    cell_programs(cell_name)
    assert cell_programs.lowered[cell_name][program] == LOWERED_BEFORE_PASSES[cell_name, program]


# The looped cell: 48 layers run 4 times over 192 cache planes. Beside its arguments
# (5.34 GB of weights and the 6.44 GB slot cache) a decode step holds less than a
# megabyte. Over the q, k and v kernels in the model's layout it held the three
# projection stacks a second time, 0.40 GB each (1.21 GB): XLA laid them out by head
# once a step, outside both loops; the engine installs them as one 2-D kernel a stack.
@pytest.mark.parametrize("case", ["holds_the_cache_once", "no_cache_sized_copy_or_put_back",
                                  "one_layer_body_not_four"])
def test_the_looped_cell_s_decode_program(cell_programs, case):
    from chipbench import aot

    cell, programs = cell_programs("ouro_serve_reason")
    cfg, eng = cell.config, cell.workload["engine"]
    planes = cfg["total_ut_steps"] * cfg["num_hidden_layers"]
    side = (planes * eng["n_slots"] * eng["max_len"] * cfg["num_key_value_heads"]
            * cfg["head_dim"])
    decode = programs["decode"]
    if case == "holds_the_cache_once":
        m = aot.memory_of(decode)
        assert planes == 192 and 2 * 2 * side == 6_442_450_944
        assert m["aliased"] >= 2 * 2 * side                       # donated, written in place
        assert 11.7e9 < m["arguments"] < 11.9e9                   # the weights once, the cache once
        assert m["temporaries"] < 2**26                           # 64 MB: no stack copied
        assert m["device_bytes"] < 13.1e9
    elif case == "no_cache_sized_copy_or_put_back":
        moved = [name for n, op, name in _arrays(decode.as_text(), scheduled_only=True)
                 if n == side and (op == "copy" or "dynamic-update-slice" in name)]
        assert not moved
    else:
        # two nested loops, and the MLP's three matmuls once in the text: an unroll
        # of the passes would hold them (and compile them) four times
        text = decode.as_text()
        assert len(re.findall(r" while\(", text)) == 2
        wide = [name for n, op, name in _arrays(text, scheduled_only=True)
                if n == eng["n_slots"] * cfg["intermediate_size"] and op == "fusion"]
        assert 1 <= len(wide) <= 2, wide
        assert "ut_pass" in text and text.count("ut_pass") >= 1


# -- a prompt chunk that rides the decode step: one program, the weights read once ----


@pytest.mark.parametrize("case", ["holds_the_kernel_once", "no_slot_lifted_out_or_put_back",
                                  "no_logits_over_the_chunk", "temporaries_under_prefill"])
@pytest.mark.parametrize("cell_name", ["mistral_serve_steady", "mixtral_serve_decode",
                                       "ouro_serve_reason"])
def test_the_decode_step_a_chunk_rides(cell_programs, cell_name, case):
    """At each cell's shapes and its largest rung (256 rows a chunk)."""
    from accelerate_tpu.serving import default_prefill_ladder
    from chipbench import aot

    cell, programs = cell_programs(cell_name)
    fused = cell_programs.fused(cell_name)
    text = fused.as_text()
    cfg, eng = cell.config, cell.workload["engine"]
    b, t = eng["n_slots"], eng["max_len"]
    hkv, d, vocab = cfg["num_key_value_heads"], cfg["head_dim"], cfg["vocab_size"]
    planes = cfg.get("total_ut_steps", 1) * cfg["num_hidden_layers"]
    c = max(default_prefill_ladder(t))
    if case == "holds_the_kernel_once":
        # the decode rows read through the kernel over both whole stacks
        kernels = [line for line in text.splitlines()
                   if "custom-call(" in line and "tpu_custom_call" in line]
        assert len(kernels) == 1 and "%decode_attention" in kernels[0]
        assert kernels[0].count(f"bf16[{planes},{b},{t},{hkv},{d}]") >= 2
    elif case == "no_slot_lifted_out_or_put_back":
        # prefill's take_slot (one slot's every plane, as its text shows) and put_slot
        # (the stack rebuilt around it); a chunk reads its slot's plane of one layer
        # where it lies
        slot = f"[{planes},1,{t},{hkv},{d}]"
        assert slot in programs["prefill"].as_text() and slot not in text
        moved = [name for n, op, name in _arrays(text, scheduled_only=True)
                 if n == planes * b * t * hkv * d
                 and (op == "copy" or "dynamic-update-slice" in name)]
        assert not moved
    elif case == "no_logits_over_the_chunk":
        # the head on the decode rows and the chunk's last prompt row alone
        assert f"f32[{b + 1},{vocab}]" in text
        assert not [s for s in (f"[{c},{vocab}]", f"[1,{c},{vocab}]", f"[{b + c},{vocab}]",
                                f"[1,{b + c},{vocab}]") if s in text]
    else:
        limit = aot.memory_of(programs["prefill"])["temporaries"]
        if "num_local_experts" in cfg:
            # the gate and up products, (tokens, E, F) each: at 256 tokens the chip's
            # compiler keeps both on chip for prefill; at 32 + 256 one of them goes to
            # HBM (PERF.md, section 6)
            limit += (b + c) * cfg["num_local_experts"] * cfg["intermediate_size"] * 2
        assert aot.memory_of(fused)["temporaries"] < limit


def test_the_decode_step_a_chunk_rides_reads_the_experts_where_they_lie(cell_programs):
    cell, _ = cell_programs("mixtral_serve_decode")
    cfg = cell.config
    experts = cfg["num_local_experts"] * cfg["hidden_size"] * cfg["intermediate_size"]
    moving = ("copy", "gather", "broadcast", "dynamic-slice", "dynamic-update-slice")
    text = cell_programs.fused("mixtral_serve_decode").as_text()
    assert "moe" in text
    moved = [name for n, op, name in _arrays(text, scheduled_only=True)
             if n == experts and any(word in op or word in name for word in moving)]
    assert not moved


# -- q, k and v read where the stack holds them ------------------------------------


@pytest.mark.parametrize("program", ["decode", "decode_chunk"])
@pytest.mark.parametrize("cell_name", ["mistral_serve_steady", "mixtral_serve_decode",
                                       "ouro_serve_reason"])
def test_the_projection_weights_are_read_where_they_lie(cell_programs, cell_name, program):
    """Over the ``(H, n, D)`` kernels of the model's layout XLA staged each layer's q,
    k and v slice into the chip's scratch memory (``S(1)``) and copied it by head
    inside the dot (steady and decode cells), or copied the three whole stacks by
    head once a step (the looped cell): neither is left over the one ``(L, H, (Hq +
    2·Hkv)·D)`` kernel the engine installs. No copy, of a layer's kernel or of a
    stack, and no staging of one, whatever its width."""
    cell, programs = cell_programs(cell_name)
    text = (programs["decode"] if program == "decode" else cell_programs.fused(cell_name)).as_text()
    cfg = cell.config
    h, d, layers = cfg["hidden_size"], cfg["head_dim"], cfg["num_hidden_layers"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    # a layer's kernel or the stack, by head or flat, axes of one dropped
    kernels = {s for heads in (hq, hkv, hq + 2 * hkv) for s in ((h, heads, d), (h, heads * d))}
    kernels |= {(layers,) + s for s in kernels}
    found = [(op, name, layout, inside_fusion)
             for dims, op, name, layout, inside_fusion in _instructions(text)
             if tuple(n for n in dims if n != 1) in kernels]
    copied = [name for op, name, _, _ in found if "copy" in op + name]
    staged = [name for op, name, layout, inside_fusion in found
              if not inside_fusion and "S(1)" in layout and op != "parameter"]
    assert not copied and not staged
