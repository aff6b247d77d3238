"""Layer names on the device and the train loop's phases on the
profiler's clock.

* every matmul of the compiled train step carries a
  :data:`repro.obs.DEVICE_SCOPES` word in its ``op_name``, and the
  layers show up in the forward and in the backward (``transpose``);
* the serving engine's programs are named after what they do;
* a ``Telemetry`` span opens a profiler annotation: under
  ``jax.profiler.trace`` the trainer's ``step`` holds ``batch``,
  ``dispatch`` and ``loss_read`` on the ``/host:`` plane of the
  ``.xplane.pb``.
"""
import re

import jax
import pytest

from repro.obs import DEVICE_SCOPES, Telemetry, hlo_scopes, scope_of

_MATMUL = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = \S+ (?:dot|convolution)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


@pytest.fixture(scope="module")
def cfg():
    from repro.configs import smoke_config
    return smoke_config("qwen2.5-3b").scaled(grad_accum=1)


def _trainer(cfg, tel=None):
    from repro.train.trainer import SpareTrainer
    return SpareTrainer(cfg, n_groups=4, redundancy=2, seq=32,
                        per_type_batch=1, total_steps=50, telemetry=tel)


@pytest.fixture(scope="module")
def step_ops(cfg):
    """``(op_name, is_matmul)`` of every instruction of the compiled
    train step that has an ``op_name``."""
    text = _trainer(cfg).compiled_step_text()
    ops = []
    for line in text.splitlines():
        op = _OP_NAME.search(line)
        if op:
            ops.append((op.group(1), bool(_MATMUL.match(line))))
    return ops


@pytest.mark.parametrize("op_name,scope", [
    ("jit(f)/transpose(jvp())/while/body/closed_call/attention/dot_general",
     "attention"),
    ("jit(f)/while/body/closed_call/jvp(head)/mul", "head"),
    ("jit(f)/transpose(jvp(head))/dot_general", "head"),
    ("jit(f)/checkpoint/rematted_computation/mlp/dot_general", "mlp"),
    ("jit(train_step)/optimizer/sqrt", "optimizer"),
    ("jit(f)/transpose(jvp())/while/body/ssm/ssd/while/body/exp", "ssd"),
    ("jit(f)/while/body/dynamic_update_slice", None),
    ("jit(headless)/mlpx/add", None),
])
def test_scope_of_takes_the_innermost_vocabulary_word(op_name, scope):
    assert scope_of(op_name) == scope


def test_hlo_scopes_reads_module_and_instructions():
    text = ('HloModule jit_step, is_scheduled=true\n\n'
            '%fused_computation (q: f32[2]) -> f32[2] {\n'
            '  %q = f32[2]{0} parameter(0)\n'
            '  %neg.4 = f32[2]{0} negate(%q), '
            'metadata={op_name="jit(step)/transpose(jvp(head))/neg"}\n'
            '  ROOT %bitcast.5 = f32[2]{0} bitcast(%neg.4)\n}\n\n'
            'ENTRY %main.3 (p: f32[2]) -> f32[2] {\n'
            '  %p = f32[2]{0} parameter(0)\n'
            '  %dot.1 = f32[2]{0} dot(f32[2]{0} %p, f32[2]{0} %p), '
            'metadata={op_name="jit(step)/jvp(mlp)/dot_general"}\n'
            '  %fusion.6 = f32[2]{0} fusion(%dot.1), kind=kLoop, '
            'calls=%fused_computation\n'
            '  %splash_mqa_fwd.7 = f32[2]{0} custom-call(%p), '
            'custom_call_target="tpu_custom_call", frontend_attributes='
            '{kernel_metadata={"xprof_metadata":"{\\"block_q\\": 512\n'
            '}}, metadata={op_name="jit(step)/attention/vmap(jit('
            '_splash_attention))/pallas_call"}\n'
            '  ROOT %add.2 = f32[2]{0} add(%fusion.6, %p), '
            'metadata={op_name="jit(step)/add"}\n}\n')
    module, scopes = hlo_scopes(text)
    assert module == "jit_step"
    # the fusion has no op_name of its own: its body's scope is taken;
    # the kernel's custom call spans two lines, its metadata on the second
    assert scopes == {"q": None, "neg.4": "head", "bitcast.5": None,
                      "p": None, "dot.1": "mlp", "fusion.6": "head",
                      "splash_mqa_fwd.7": "attention", "add.2": None}


def test_every_matmul_of_the_train_step_is_scoped(step_ops):
    matmuls = [op for op, is_mm in step_ops if is_mm]
    assert len(matmuls) >= 20
    unscoped = [op for op in matmuls if scope_of(op) is None]
    assert not unscoped


@pytest.mark.parametrize("scope", ["embed", "attention", "mlp", "head"])
def test_layer_scope_in_forward_and_backward(step_ops, scope):
    mine = [op for op, _ in step_ops if scope_of(op) == scope]
    assert any("transpose(" not in op for op in mine), "no forward op"
    assert any("transpose(" in op for op in mine), "no backward op"


@pytest.mark.parametrize("scope", ["grad_accum", "optimizer"])
def test_step_scope_outside_the_backward(step_ops, scope):
    mine = [op for op, _ in step_ops if scope_of(op) == scope]
    assert mine and not any("transpose(" in op for op in mine)


def test_scopes_are_the_vocabulary(step_ops):
    found = {scope_of(op) for op, _ in step_ops} - {None}
    assert found == {"embed", "attention", "mlp", "head", "grad_accum",
                     "optimizer"}
    assert found <= set(DEVICE_SCOPES)


def test_ssd_scan_scoped_inside_the_ssm_mixer():
    """On a tiny Mamba-2 step the SSD scan's operations, forward and
    backward, land under ``ssd``, and the mixer's projections under
    ``ssm``."""
    from repro.configs import smoke_config
    from repro.train.trainer import SpareTrainer
    cfg = smoke_config("mamba2-1.3b").scaled(grad_accum=1)
    text = SpareTrainer(cfg, n_groups=4, redundancy=2, seq=32,
                        per_type_batch=1,
                        total_steps=50).compiled_step_text()
    ops = [(op.group(1), bool(_MATMUL.match(line)))
           for line in text.splitlines()
           for op in [_OP_NAME.search(line)] if op]
    for scope in ("ssm", "ssd"):
        mine = [op for op, is_mm in ops if is_mm and scope_of(op) == scope]
        assert any("transpose(" not in op for op in mine), scope
        assert any("transpose(" in op for op in mine), scope
    assert all("/ssm/" in op for op, _ in ops if scope_of(op) == "ssd")
    assert {scope_of(op) for op, is_mm in ops if is_mm} == {
        "ssm", "ssd", "head"}


def test_serve_programs_are_named(cfg):
    from repro.models.model import build_model
    from repro.serve import ServeEngine, pool_pages_for
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    engine = ServeEngine(model, params, n_slots=2,
                         n_pages=pool_pages_for(2, 12, 4), page_size=4,
                         max_new=4, buckets=(8,))
    engine.warmup()
    names = {key[0]: hlo_scopes(text)[0]
             for key, text, _ in engine.cache.programs()}
    assert names == {"decode": "jit_serve_decode",
                     "prefill": "jit_serve_prefill",
                     "write": "jit_serve_cache_write"}


def _host_events(trace_dir, names):
    from jax.profiler import ProfileData
    (path,) = trace_dir.rglob("*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name in names]


@pytest.mark.parametrize("record", [True, False],
                         ids=["recorded", "metrics-only"])
def test_train_phases_on_the_profiler_host_plane(cfg, tmp_path, record):
    from repro.train.trainer import TrainReport
    tr = _trainer(cfg, Telemetry(trace=record))
    report = TrainReport()
    tr.train_step(report)                  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        tr.train_step(report)
    phases = ("batch", "dispatch", "loss_read")
    events = _host_events(tmp_path, {"step", *phases})
    (step,) = [ev for ev in events if ev[0] == "step"]
    inner = sorted((ev for ev in events if ev[0] != "step"),
                   key=lambda ev: ev[1])
    assert tuple(ev[0] for ev in inner) == phases
    for _, start, end in inner:
        assert step[1] <= start <= end <= step[2]
