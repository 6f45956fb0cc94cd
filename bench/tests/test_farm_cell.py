"""The data-parallel farm's cell (``mnist_class.farm_train.c4.mesh4``): a
whole run on four CPU devices at a small size, the missing exchange
caught, the control, and the readers of the farm's reconciliation and
outer-product kernel on hand-made intervals and on a trace recorded on a
four-chip TPU v5e host (``data/farm_train.xplane.pb``: one step of the
cell's first version under the benchmark's spans, 721 KB).

A run on four devices needs a process of its own (JAX fixes its device
count when it starts), so those tests run the harness in a child."""
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from bench import collectives
from bench import reference as ref
from bench import run as harness
from bench import trace as tr
from bench import work
from bench.kinds import train

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "mnist_class.farm_train.c4.mesh4"
TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "farm_train.xplane.pb")
SEED = 3_000_000_011

RUN = """
import json, sys
sys.path[:0] = [{root!r}, {src!r}]
import jax.numpy as jnp
from bench import run as harness
if {skip_exchange!r}:
    # the reconciliation without its exchange: each device keeps the sum
    # of its own chips' outer products
    from repro.dist import collectives
    collectives.farm_reduce_sum = (
        lambda contrib, *, chip_axis=0, **kw: jnp.sum(contrib, chip_axis))
res = harness.run({cell!r}, {seed}, 0.5, False, require_chip=False,
                  overrides={{"config": {{"dims": [100, 40, 10],
                                          "core_rows": 40,
                                          "core_cols": 10}},
                              "traffic": {{"pool": 4}}}})
print(json.dumps({{"correct": res["correct"], "checks": res["checks"],
                   "count": res["device"]["count"]}}))
"""


def four_device_run(skip_exchange=False):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = RUN.format(root=ROOT, src=os.path.join(ROOT, "src"), cell=CELL,
                      seed=SEED, skip_exchange=skip_exchange)
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sound_farm_is_correct_on_four_devices():
    res = four_device_run()
    assert res["count"] == 4
    assert res["correct"], res["checks"]
    assert res["checks"]["replica_gap"]["value"] == 0.0


def test_skipped_exchange_fails_grad_gap():
    res = four_device_run(skip_exchange=True)
    checks = res["checks"]
    assert not res["correct"], checks
    assert checks["grad_gap"]["value"] > checks["grad_gap"]["limit"]


@pytest.mark.parametrize("seed", (3_000_000_101, 3_000_000_102))
def test_control_and_missing_exchange_are_not_correct(seed):
    """Computed one precision below the configuration's, or without the
    exchange, the reference reads not correct under the cell's limits;
    the exchange's fault by ``grad_gap``."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    w = next(w for w in bench["workloads"] if w["name"] == CELL)
    cfg = json.load(open(os.path.join(harness.BENCH, "configs",
                                      w["config"] + ".json")))
    tr_ = json.load(open(os.path.join(harness.BENCH, "traffic",
                                      w["traffic"] + ".json")))
    limits = json.load(open(os.path.join(harness.BENCH, "limits",
                                         CELL + ".json")))
    kind = harness.load_module(os.path.join(harness.BENCH, "kinds",
                                            "farm_train.py"))
    spec = ref.Spec(cfg)
    g0 = [tuple(np.asarray(a) for a in p)
          for p in ref.initial_layers(cfg, seed)]
    b, d = int(tr_["batch"]), cfg["dims"]
    xs = ref.uniform_pool(seed, 1, (train.REF_STEPS, b, d[0]))
    ts = ref.uniform_pool(seed, 2, (train.REF_STEPS, b, d[-1]))
    batches = [(xs[i], ts[i]) for i in range(train.REF_STEPS)]
    lr = float(tr_["lr"])
    refd = train.reference_steps(g0, batches, lr, spec)
    ctl = train.compare(g0, train.reference_steps(
        g0, batches, lr, spec, "bf16_3x"), refd)
    assert ctl["err_gap_median"] > limits["err_gap_median"], ctl
    skipped = train.compare(g0, train.reference_steps(
        g0, batches, lr, spec,
        step=kind.exchange_skipped(int(cfg["replicas"]))), refd)
    assert skipped["grad_gap"] > limits["grad_gap"], skipped


def test_replica_gap():
    kind = harness.load_module(os.path.join(harness.BENCH, "kinds",
                                            "farm_train.py"))
    a = [(np.zeros((2, 3)), np.ones((2, 3)))]
    b = [(np.zeros((2, 3)), np.ones((2, 3)))]
    assert kind.replica_gap([a, b, b]) == 0.0
    c = [(np.zeros((2, 3)), np.full((2, 3), 1.25))]
    assert kind.replica_gap([a, b, c]) == 0.25
    assert kind.replica_gap([a]) == 0.0


# ---------------------------------------------------------------------------
# Readers on hand-made intervals
# ---------------------------------------------------------------------------

def reader(name):
    return harness.load_module(os.path.join(harness.BENCH, "metrics",
                                            name + ".py"))


ALL_REDUCE = ("%psum.1 = f32[6,400,100]{2,1,0} all-reduce(f32[6,400,100]"
              "{2,1,0} %x), channel_id=1, replica_groups={{0,1,2,3}}")
START = "%all-reduce-start.2 = f32[] all-reduce-start(f32[] %m)"
DONE = "%all-reduce-done.2 = f32[] all-reduce-done(f32[] %all-reduce-start.2)"
DW = ('%custom-call.7 = f32[6,400,128]{2,1,0} custom-call(f32[6,64,400]'
      '{2,1,0} %a, f32[6,64,128]{2,1,0} %b), custom_call_target='
      '"tpu_custom_call"')
WHILE = "%while.3 = (s32[]) while((s32[]) %t), body=%body"


def hand_made(ops_by_device, calls=2, samples=512):
    ev = tr.Event
    devs = [tr.Device(i, ops=[ev(n, s, e) for n, s, e in ops])
            for i, ops in enumerate(ops_by_device)]
    red = tr.Reduced((0, 1000), devs, [ev(tr.WINDOW_SPAN, 0, 1000)])
    cfg = json.load(open(os.path.join(harness.BENCH, "configs",
                                      "mnist_class_farm4.json")))
    return types.SimpleNamespace(
        red=red, calls=calls, samples=samples, cfg=cfg, chips=len(devs),
        work=work, peak={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})


def test_collectives_told_by_opcode():
    assert collectives.opcode(ALL_REDUCE) == ("all-reduce", "")
    assert collectives.opcode(START) == ("all-reduce", "-start")
    assert collectives.opcode(DONE) == ("all-reduce", "-done")
    assert collectives.opcode(
        "%all-gather.1 = f32[8]{0} all-gather(f32[2]{0} %a)") \
        == ("all-gather", "")
    for other in (DW, WHILE, "%fusion.4 = f32[6]{0} fusion(f32[6]{0} "
                  "%psum.1), kind=kLoop, calls=%all-reduce.body"):
        assert collectives.opcode(other) is None


@pytest.mark.parametrize("ops,total_ns,exposed_ns", [
    # a synchronous all-reduce, 30 of its 100 ns under other work; the while
    # that holds both hides nothing
    ([[(WHILE, 0, 900), ("%fusion.1 = f32[] fusion()", 100, 150),
       (ALL_REDUCE, 120, 220)]], 100, 70),
    # an asynchronous pair counts from start to done; two devices average
    ([[(START, 100, 110), ("%fusion.2 = f32[] fusion()", 110, 300),
       (DONE, 300, 320)],
      [(START, 100, 110), (DONE, 200, 220)]], (220 + 120) / 2,
     (30 + 120) / 2),
    # fully hidden: exposed reads 0.0, not None
    ([[("%fusion.3 = f32[] fusion()", 0, 500), (ALL_REDUCE, 100, 200)]],
     100, 0),
])
def test_collective_readers(ops, total_ns, exposed_ns):
    r = hand_made(ops)
    assert reader("collective_ms_per_call").read(r) == pytest.approx(
        total_ns * 1e-6 / 2)
    assert reader("collective_exposed_ms_per_call").read(r) \
        == pytest.approx(exposed_ns * 1e-6 / 2)


@pytest.mark.parametrize("name", ["collective_ms_per_call",
                                  "collective_exposed_ms_per_call",
                                  "dw_kernel_roofline"])
def test_readers_return_none_with_nothing_to_read(name):
    m = reader(name)
    assert m.read(hand_made([[("%fusion.1 = f32[] fusion()", 0, 10)]])) \
        is None
    assert m.read(hand_made([])) is None
    assert m.read(hand_made([[(ALL_REDUCE, 0, 10), (DW, 20, 40)]],
                            calls=0)) is None


def test_dw_kernel_roofline_on_hand_made_kernels():
    assert tr.kernel_kind(DW) == "dw"
    r = hand_made([[(DW, 0, 100_000)], [(DW, 0, 300_000)]])
    least, bound = work_of(r).least_seconds(r.peak)
    assert bound == "bandwidth"
    # the least time on the two chips over the mean device time
    assert reader("dw_kernel_roofline").read(r) == pytest.approx(
        100 * (least / 2) / 200e-6)


def work_of(r):
    from bench import work_farm
    return work_farm.dw_work(r.cfg, r.samples / r.calls,
                             r.cfg["replicas"]) * r.calls


def test_dw_work_counts_real_cores_and_rows():
    from bench import work_farm
    cfg = {"core_rows": 400, "core_cols": 100,
           "dims": [784, 300, 200, 100, 10]}
    n = sum(work.real_cores(cfg))
    assert n == 10
    w = work_farm.dw_work(cfg, 256, 4)
    assert w.flops == n * 2 * 256 * 400 * 100
    assert w.bytes == n * 4 * (256 * 400 + 256 * 100 + 4 * 400 * 100)


# ---------------------------------------------------------------------------
# Readers on a trace recorded on a four-chip v5e host
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    red = tr.reduce(TRACE)
    calls = sum(s.name == "bench.call" for s in red.spans)
    cfg = json.load(open(os.path.join(harness.BENCH, "configs",
                                      "mnist_class_farm4.json")))
    return types.SimpleNamespace(
        red=red, calls=calls, samples=calls * 256, cfg=cfg, chips=4,
        passes=3, work=work, peak=work.peak("TPU v5 lite"))


def collective_names(dev):
    """(instruction name without its number, opcode) of each collective."""
    out = {}
    for o in dev.ops:
        kind = collectives.opcode(o.name)
        if kind:
            inst = o.name.split(" = ", 1)[0].lstrip("%").split(".")[0]
            out[(inst,) + kind] = out.get((inst,) + kind, 0) + 1
    return out


def test_recorded_trace_devices_and_programs(recorded):
    red = recorded.red
    assert [d.index for d in red.devices] == [0, 1, 2, 3]
    assert recorded.calls == 1
    for d in red.devices:
        progs = {m.name.split("(")[0] for m in d.modules}
        # the sharded step runs once on every chip; the first version also
        # moved the index maps each step (gathers, broadcasts)
        assert sum(m.name.startswith("jit_chip_train") for m in d.modules) \
            == 1
        assert progs <= {"jit_chip_train", "jit_gather",
                         "jit_broadcast_in_dim", "jit_convert_element_type"}


def test_recorded_collectives_as_the_v5e_prints_them(recorded):
    """Each chip's step holds, per stage, the psum of the outer products
    and the pmax of the error full-scale: synchronous all-reduces named by
    the program.  The unnamed all-reduces are the first version's per-stage
    gathers of chip 0's stacks."""
    for d in recorded.red.devices:
        assert collective_names(d) == {("psum", "all-reduce", ""): 4,
                                       ("pmax", "all-reduce", ""): 4,
                                       ("all-reduce", "all-reduce", ""): 4}
        assert len(collectives.intervals(d)) == 12


@pytest.mark.parametrize("name,value", [
    ("collective_ms_per_call", 0.167805),
    ("collective_exposed_ms_per_call", 0.167805),
    ("dw_kernel_roofline", 22.069056837476566),
    ("launches_per_call", 11.0),
    ("device_idle_share", 98.08209326050789),
])
def test_readers_on_the_recorded_trace(recorded, name, value):
    assert reader(name).read(recorded) == pytest.approx(value, rel=1e-9)
