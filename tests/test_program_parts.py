"""The model programs' named scopes and the compile ledger's op maps
(`ray_tpu/util/device_profile.py`, `ray_tpu/util/tracing.py`): what places a
profiler trace's device ops in the model."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import optax
import pytest

from ray_tpu.models import TransformerConfig, init_params, make_train_step
from ray_tpu.models.generate import (_decode_step_slots, init_kv_cache,
                                     init_slot_cache, prefill_chunk)
from ray_tpu.util import device_profile as dp
from ray_tpu.util import tracing

PATHS = [
    ("jit(step)/jvp()/while/body/closed_call/attention/dot_general",
     ("attention", "forward")),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "attention/mul", ("attention", "backward")),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/attention/exp", ("attention", "recompute")),
    # the innermost scope wins
    ("jit(fused_step)/while/body/projections/norm/mul", ("norm", "forward")),
    ("jit(fused_step)/while/body/experts/ffn/dot_general",
     ("ffn", "forward")),
    # a scope opened outside the transformation is wrapped by it
    ("jit(step)/while/body/closed_call/transpose(jvp(norm))/reduce_sum",
     ("norm", "backward")),
    ("jit(step)/while/body/closed_call/jvp(head)/bsd,dv->bsv/dot_general",
     ("head", "forward")),
    # no scope: the loop's own instructions, a function that is named
    # like a part
    ("jit(step)/jvp()/while/body/dynamic_update_slice", (None, "forward")),
    ("jit(step)/transpose(jvp())/while", (None, "backward")),
    ("jit(head)/add", (None, "forward")),
    ("", (None, "forward")),
]


@pytest.mark.parametrize("path,want", PATHS)
def test_part_of(path, want):
    assert dp.part_of(path) == want


HLO = """\
HloModule jit_fused_step, is_scheduled=true, entry_computation_layout={()->f32[]}

%fused_computation.1 (p0: f32[8,8], p1: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %p1 = f32[8,8]{1,0} parameter(1)
  %convolution.3 = f32[8,8]{1,0} convolution(%p0, %p1), dim_labels=bf_io->bf, metadata={op_name="jit(fused_step)/while/body/ffn/bsf,fd->bsd/dot_general"}
  ROOT %add.9 = f32[8,8]{1,0} add(%convolution.3, %p0), metadata={op_name="jit(fused_step)/while/body/add"}
}

%fused_computation.2 (p0.1: f32[8,8]) -> (f32[8,8], f32[8]) {
  %p0.1 = f32[8,8]{1,0} parameter(0)
  %mul.1 = f32[8,8]{1,0} multiply(%p0.1, %p0.1), metadata={op_name="jit(fused_step)/while/body/norm/mul"}
  %reduce.1 = f32[8]{0} reduce(%mul.1, %p0.1), dimensions={1}, to_apply=%sum, metadata={op_name="jit(fused_step)/while/body/norm/reduce_sum"}
  %add.2 = f32[8,8]{1,0} add(%p0.1, %p0.1), metadata={op_name="jit(fused_step)/while/body/add"}
  ROOT %tuple.7 = (f32[8,8]{1,0}, f32[8]{0}) tuple(%add.2, %reduce.1)
}

%sum (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.1 = f32[] add(%a, %b)
}

%body (arg: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %arg = (s32[], f32[8,8]{1,0:T(8,128)}) parameter(0)
  %gte.1 = f32[8,8]{1,0} get-tuple-element(%arg), index=1
  %bitcast_add_fusion.3 = f32[8,8]{1,0:T(8,128)S(1)} fusion(%gte.1, %gte.1), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(fused_step)/while/body/add"}
  %fusion.5 = (f32[8,8]{1,0}, f32[8]{0}) fusion(%bitcast_add_fusion.3), kind=kLoop, calls=%fused_computation.2
  %copy.4 = f32[8,8]{1,0} copy(%gte.1)
  ROOT %tuple.2 = (s32[], f32[8,8]{1,0}) tuple(%gte.1, %copy.4)
}

%cond (arg.1: (s32[], f32[8,8])) -> pred[] {
  %arg.1 = (s32[], f32[8,8]{1,0}) parameter(0)
  ROOT %lt.1 = pred[] compare(%arg.1, %arg.1), direction=LT, metadata={op_name="jit(fused_step)/while/cond/lt"}
}

ENTRY %main.9 (x: f32[8,8]) -> f32[] {
  %x = f32[8,8]{1,0} parameter(0)
  %c = s32[] constant(0)
  %copy.7 = f32[8,8]{0,1} copy(%x)
  %bitcast.2 = f32[8,8]{1,0} bitcast(%copy.7)
  %tuple.1 = (s32[], f32[8,8]{1,0}) tuple(%c, %bitcast.2)
  %while.2 = (s32[], f32[8,8]{1,0}) while(%tuple.1), condition=%cond, body=%body, metadata={op_name="jit(fused_step)/while"}
  %flash_attention_fwd.4 = f32[8,8]{1,0} custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(fused_step)/attention/flash_attention_fwd/pallas_call"}
  ROOT %reduce.9 = f32[] reduce(%flash_attention_fwd.4, %c), dimensions={0,1}, to_apply=%sum, metadata={op_name="jit(fused_step)/head/reduce_sum"}
}
"""


def test_op_map_reads_what_a_trace_can_show():
    m = dp.op_map(HLO)
    assert m["module"] == "jit_fused_step"
    ins = m["instructions"]
    # the entry's and the loop's instructions, nothing of a fusion's inside
    # or of a reduce's scalar function, no parameter, tuple or constant
    assert set(ins) == {"while.2", "flash_attention_fwd.4", "reduce.9",
                        "bitcast_add_fusion.3", "fusion.5", "copy.4", "lt.1",
                        "copy.7"}
    # an output fusion goes by its matmul, not by the add that is its root
    assert dp.part_of(ins["bitcast_add_fusion.3"])[0] == "ffn"
    # a fusion without one by the part most of its instructions carry,
    # whatever its root (a tuple) says
    assert dp.part_of(ins["fusion.5"])[0] == "norm"
    assert dp.part_of(ins["flash_attention_fwd.4"])[0] == "attention"
    assert ins["copy.4"] == "" and dp.part_of(ins["while.2"])[0] is None
    # a copy the compiler made in front of the loop is the cost of the part
    # that first reads its element in the body
    assert ins["copy.7"] == ins["bitcast_add_fusion.3"]
    assert m["named"] == 5 and len(m["shape"]) == 12


@pytest.fixture
def fresh_ledger(monkeypatch):
    monkeypatch.setattr(tracing, "_programs", {})
    monkeypatch.setattr(tracing, "_unmapped", [])
    return tracing.program_maps


def _compiled_spans(program):
    return [e for e in tracing.span_events()
            if e["name"] == "program:compiled"
            and e["args"]["program"] == program]


def test_ledger_writes_one_map_a_program_and_shape(fresh_ledger, tmp_path):
    @jax.named_scope("ffn")
    def ledger_probe(x, w):
        return jnp.tanh(x @ w)

    prof = dp.DispatchProfiler()
    before = len(_compiled_spans("probe"))
    # what the engine hands `wrap`: a closure over a jit that DONATES
    jitted = jax.jit(ledger_probe, donate_argnums=(0,))

    @functools.wraps(jitted)
    def counting(*a):
        return jitted(*a)

    fn = prof.wrap("probe", counting)
    w = jnp.ones((8, 8))
    fn(jnp.ones((4, 8)), w)
    maps = fresh_ledger()["probe"]
    assert len(maps) == 1 and maps[0]["module"] == "jit_ledger_probe"
    assert any(dp.part_of(v)[0] == "ffn"
               for v in maps[0]["instructions"].values())
    fn(jnp.ones((4, 8)), w)                  # the same shape: nothing new
    assert len(fresh_ledger()["probe"]) == 1
    fn(jnp.ones((16, 8)), w)                 # a first-seen shape: one more
    assert len(fresh_ledger()["probe"]) == 2
    spans = _compiled_spans("probe")[before:]
    assert len(spans) == 2 and spans[0]["cat"] == "setup"
    assert spans[0]["args"]["module"] == "jit_ledger_probe"
    assert spans[0]["args"]["instructions"] == len(maps[0]["instructions"])
    # the files go with the process's span file
    assert tracing.write_span_file(str(tmp_path))
    path = tmp_path / "programs" / \
        f"{tracing._proc['kind']}-{os.getpid()}.probe.json"
    body = json.loads(path.read_text())
    assert body["program"] == "probe" and len(body["maps"]) == 2


CONFIGS = {
    "dense": dict(),
    "routed": dict(n_experts=4, expert_top_k=2, router="sigmoid",
                   n_shared_experts=1),
    "conv": dict(layer_kinds=("conv", "full"), n_layers=2),
}
# the parts every model shows in each program, and those of one model only
SERVED = {"embed", "norm", "projections", "attention", "cache_write", "ffn",
          "head"}
TRAINED = (SERVED - {"cache_write"}) | {"optimizer"}
ONLY = {"routed": "experts", "conv": "conv"}


def _parts_of(compiled):
    m = dp.op_map(compiled.as_text())
    with_name = [v for v in m["instructions"].values() if v]
    return ({dp.part_of(v)[0] for v in with_name} - {None},
            m["named"] / len(with_name))


@pytest.fixture(scope="module", params=sorted(CONFIGS))
def tiny(request):
    cfg = TransformerConfig.tiny(**CONFIGS[request.param])
    params, _ = init_params(jax.random.PRNGKey(0), cfg)
    return request.param, cfg, params


@pytest.mark.parametrize("program", ["train_step", "fused_step", "chunk"])
def test_compiled_programs_name_their_instructions(tiny, program):
    """Of the instructions that carry an ``op_name`` (the CPU compiler's own
    copies and reduce-windows carry none) at least 85 % lie in a part (87-99
    at these sizes; 91-98 % of ALL instructions in the benchmark's programs
    compiled for a v5e): the rest is the loops' own (counters, conditions,
    the stacks of saved activations), a handful whatever the size, and the
    residual adds."""
    name, cfg, params = tiny
    if program == "train_step":
        opt = optax.adamw(1e-3)
        step = jax.jit(make_train_step(cfg, opt, accum_steps=2),
                       donate_argnums=(0, 1))
        compiled = step.lower(params, opt.init(params), {
            "tokens": jnp.zeros((4, 32), jnp.int32)}).compile()
        want = TRAINED
    elif program == "fused_step":
        f = jax.jit(functools.partial(_decode_step_slots, cfg=cfg))
        compiled = f.lower(params, jnp.zeros((4,), jnp.int32),
                           init_slot_cache(cfg, 4, 64),
                           jnp.ones((4,), bool)).compile()
        want = SERVED
    else:
        g = jax.jit(functools.partial(prefill_chunk, cfg=cfg))
        compiled = g.lower(params, jnp.zeros((1, 16), jnp.int32),
                           init_kv_cache(cfg, 1, 64),
                           n_valid=jnp.int32(5)).compile()
        want = SERVED
    found, named = _parts_of(compiled)
    own = {ONLY[name]} if name in ONLY else set()
    assert found == want | own
    assert named >= 0.85


def test_train_step_is_in_the_ledger_and_never_blocks(fresh_ledger,
                                                      monkeypatch):
    cfg = TransformerConfig.tiny()
    params, _ = init_params(jax.random.PRNGKey(0), cfg)
    opt = optax.adamw(1e-3)
    step = make_train_step(cfg, opt)
    # a plain function with no attribute of its own: `jax.jit` copies a
    # function's ``__dict__`` onto what it returns, and a ``step.lower``
    # would stand in for the caller's jit's (no donation, no shardings)
    assert not vars(step)
    jitted = jax.jit(step, donate_argnums=(0, 1))

    def no_block(*_a, **_k):
        raise AssertionError("the train step's ledger blocked")

    monkeypatch.setattr(jax, "block_until_ready", no_block)
    batch = {"tokens": jnp.zeros((2, 16), jnp.int32)}
    compiled = jitted.lower(params, opt.init(params), batch).compile()
    assert compiled.as_text().startswith("HloModule jit_step")
    assert "input_output_alias" in compiled.as_text().splitlines()[0]
    maps = fresh_ledger()["train_step"]
    assert len(maps) == 1 and maps[0]["module"] == "jit_step"
    # the map is of the executable that runs: the same instructions
    assert maps[0] == dp.op_map(compiled.as_text())
    out = compiled(params, opt.init(params), batch)
    assert set(out[2]) == {"loss", "grad_norm"}
    assert len(fresh_ledger()["train_step"]) == 1
