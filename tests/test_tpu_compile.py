"""The main path's kernels compile for a TPU v5e, checked without the chip.

The TPU compiler is installed with JAX; it compiles for a chip that is
described (``v5e:2x2``) and not attached.  Each stacked crossbar kernel is
compiled at the shapes the three phases of ``chip_smoke.py`` dispatch
(core stacks of 400x100 crossbars), and the whole compiled ``chip_train``
program of ``mnist_class`` with the Pallas kernel body, on one chip and
sharded over four, that four-chip farm's serving session, and LeNet-5's
``chip_train`` with its conv stages.  A compile
refused here (tiling, VMEM, lowering) would be refused on the chip.  The
recognition program of ``mnist_class`` at 1024 rows is pinned to its
lowered text.

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU library at a time.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs.paper_apps import NETWORKS, PAPER_SPEC
from repro.core import crossbar as xb
from repro.kernels import ops as kernel_ops
from repro.sim import VirtualChip, compiled as csim

ROWS, COLS = 400, 100       # one crossbar core of the stage stacks

# (op, cores T, microbatch M) per dispatch of the chip_smoke phases:
#   chip mnist_class: 6 cores per stage, infer 1024, train batch 64;
#   pipeline isolet_class k2: a serving session of 256 requests, one wave
#     over 60 cores a stage, train slices of 40 and 60 cores at batch 16;
#   farm kdd_anomaly c4: a serving session of 256 requests, 64 rows on
#     each of 4 chips' cores, train at 16 samples per chip through the
#     reconciled bwd + dw path.
CASES = [
    ("fwd", 6, 1024), ("fwd", 6, 64), ("fwd", 60, 256), ("fwd", 60, 16),
    ("fwd", 4, 64), ("fwd", 4, 16),
    ("train", 6, 64), ("train", 40, 16), ("train", 60, 16),
    ("bwd", 4, 16), ("dw", 4, 16), ("pulse", 6, 64),
]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip; keep it out of the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _spec(sharding, *shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_call(op, T, M, s):
    g = (_spec(s, T, ROWS, COLS), _spec(s, T, ROWS, COLS))
    xs, ds = _spec(s, T, M, ROWS), _spec(s, T, M, COLS)
    if op == "fwd":
        return (lambda x, gp, gm: kernel_ops.crossbar_fwd_stacked(
            x, gp, gm, interpret=False)), (xs, *g)
    if op == "bwd":
        return (lambda d, gp, gm: kernel_ops.crossbar_bwd_stacked(
            d, gp, gm, interpret=False)), (ds, *g)
    if op == "dw":
        return (lambda x, d: kernel_ops.crossbar_dw_stacked(
            x, d, interpret=False)), (xs, ds)
    if op == "pulse":
        return (lambda gp, gm, x, d: kernel_ops.pulse_update_stacked(
            gp, gm, x, d, lr=0.01, interpret=False)), (*g, xs, ds)
    # the compiled scan's training body: lr pre-scaled into x, no forward
    return (lambda gp, gm, x, d: kernel_ops.crossbar_train_stacked(
        gp, gm, x, d, lr=1.0, max_dw=PAPER_SPEC.max_update,
        levels=PAPER_SPEC.update_levels, w_max=PAPER_SPEC.w_max,
        compute_y=False, interpret=False)), (*g, xs, ds)


@pytest.mark.parametrize("op,T,M", CASES)
def test_stacked_kernel_compiles_for_v5e(one_chip, op, T, M):
    fn, args = _kernel_call(op, T, M, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_mnist_chip_train_compiles_for_v5e_with_kernel_body(one_chip,
                                                            monkeypatch):
    # the kernels pick interpret mode from the attached backend (the CPU
    # here); the chip's choice is steered in the test, not the program
    monkeypatch.setattr(kernel_ops, "_default_interpret", lambda: False)
    dims = NETWORKS["mnist_class"]
    key = jax.random.PRNGKey(0)
    layers = [xb.init_conductances(jax.random.fold_in(key, i), f, o,
                                   PAPER_SPEC)
              for i, (f, o) in enumerate(zip(dims, dims[1:]))]
    chip = VirtualChip(layers, PAPER_SPEC)
    st = chip._get_stacks()
    cfg = chip._cfg._replace(use_kernels=True)
    s = one_chip
    idx = {k: _spec(s, *v.shape, dtype=v.dtype)
           for k, v in st.index_pytree().items()}
    compiled = csim.chip_train.lower(
        _spec(s, *st.g_plus.shape), _spec(s, *st.g_minus.shape),
        _spec(s, 64, dims[0]), _spec(s, 64, dims[-1]), idx, cfg,
        lr_eff=_spec(s)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # the conductance stacks are donated: updated in place, not copied
    assert mem.alias_size_in_bytes >= 2 * st.g_plus.nbytes


def test_lenet5_chip_train_compiles_for_v5e_with_kernel_body(one_chip,
                                                             monkeypatch):
    """LeNet-5's training step at batch 64, the program of
    ``lenet5_mnist.chip_conv_train.b64``: the conv stages' kernels at
    their folded rows (50,176 for C1, 6,400 for C3) beside the dense
    stages' scan, one program, the stacks donated."""
    import jax.numpy as jnp

    from repro.core import conv as cref
    monkeypatch.setattr(kernel_ops, "_default_interpret", lambda: False)
    shapes, dims = list(cref.LENET5_CONV), list(cref.LENET5_DIMS)
    widths = [(c.fan_in, c.out) for c in shapes] + list(zip(dims, dims[1:]))
    layers = [{"g_plus": jnp.zeros(w), "g_minus": jnp.zeros(w)}
              for w in widths]
    chip = VirtualChip(layers, PAPER_SPEC, conv=shapes)
    st = chip._get_stacks()
    cfg = chip._cfg._replace(use_kernels=True)
    s = one_chip
    idx = jax.tree.map(lambda v: _spec(s, *v.shape, dtype=v.dtype),
                       st.index_pytree())
    compiled = csim.chip_train.lower(
        _spec(s, *st.g_plus.shape), _spec(s, *st.g_minus.shape),
        _spec(s, 64, 32 * 32), _spec(s, 64, dims[-1]), idx, cfg,
        lr_eff=_spec(s)).compile()
    text = compiled.as_text()
    # conv fwd and train kernels of each conv stage, the dense scan's two
    assert text.count("tpu_custom_call") == 6
    # one core: the kernels take the folded rows without a core axis
    assert "f32[50176,400]" in text and "f32[6400,400]" in text
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= 2 * st.g_plus.nbytes


def test_meshed_mnist_farm_step_compiles_for_four_v5e_chips(topo, one_chip,
                                                           monkeypatch):
    """The farm of four ``mnist_class`` replicas at batch 256, one replica
    per chip of a 2x2 v5e host: one program with the kernels run per chip,
    the reconciliation and the error full-scale as all-reduces, the
    envelope never gathered, and the stacks donated."""
    import re

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.sim import ChipFarm
    monkeypatch.setattr(kernel_ops, "_default_interpret", lambda: False)
    dims = NETWORKS["mnist_class"]
    key = jax.random.PRNGKey(0)
    layers = [xb.init_conductances(jax.random.fold_in(key, i), f, o,
                                   PAPER_SPEC)
              for i, (f, o) in enumerate(zip(dims, dims[1:]))]
    farm = ChipFarm(layers, PAPER_SPEC, n_chips=4)
    st, gp, _, _ = farm._get_stacks()
    cfg = csim.chip_config(st, PAPER_SPEC)._replace(use_kernels=True)
    mesh = Mesh(topo.devices, ("chips",))
    chips, rows, alike = (NamedSharding(mesh, p) for p in
                          (P(None, "chips"), P("chips"), P()))
    idx = {k: _spec(alike, *v.shape, dtype=v.dtype)
           for k, v in st.index_pytree().items()}
    compiled = csim.chip_train.lower(
        _spec(chips, *gp.shape), _spec(chips, *gp.shape),
        _spec(rows, 256, dims[0]), _spec(rows, 256, dims[-1]), idx, cfg,
        lr_eff=_spec(alike), reconcile="none", mesh=mesh).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    opcodes = re.findall(r" ([a-z-]+)\(", text)
    assert opcodes.count("all-reduce") == 2       # psum of dw, pmax
    assert not {"all-gather", "all-to-all", "collective-permute",
                "reduce-scatter"} & set(opcodes)
    # each chip holds and updates in place its replica's envelope
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= 2 * gp.nbytes // 4


def test_meshed_farm_serve_compiles_for_four_v5e_chips(topo, one_chip,
                                                      monkeypatch):
    """A serving session of four ``mnist_class`` replicas, one per chip of
    a 2x2 v5e host: one recognition wave with the kernel run per chip on
    its own lanes, over the envelope split by chip.  The lanes never
    meet: the only collective gathers the answers into request order,
    after the wave."""
    import re
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.sim import ChipFarm
    monkeypatch.setattr(kernel_ops, "_default_interpret", lambda: False)
    dims = NETWORKS["mnist_class"]
    key = jax.random.PRNGKey(0)
    layers = [xb.init_conductances(jax.random.fold_in(key, i), f, o,
                                   PAPER_SPEC)
              for i, (f, o) in enumerate(zip(dims, dims[1:]))]
    farm = ChipFarm(layers, PAPER_SPEC, n_chips=4)
    st, gp, _, _ = farm._get_stacks()
    assert gp.shape == (st.S, 4, st.T_max, st.rows, st.cols)
    cfg = csim.chip_config(st, PAPER_SPEC)._replace(use_kernels=True)
    mesh = Mesh(topo.devices, (csim.FARM_AXIS,))
    chips = NamedSharding(mesh, P(None, csim.FARM_AXIS))
    alike = NamedSharding(mesh, P())
    idx = {k: _spec(alike, *v.shape, dtype=v.dtype)
           for k, v in st.index_pytree().items()}
    q_pad = 16                     # 64 one-row requests over 4 lanes
    compiled = csim.serve_scan.lower(
        _spec(chips, *gp.shape), _spec(chips, *gp.shape),
        _spec(alike, 4 * q_pad, 1, dims[0]), idx, cfg,
        mesh=mesh).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    opcodes = re.findall(r" ([a-z-]+)\(", text)
    assert opcodes.count("all-gather") == 1          # the answers
    assert not {"all-reduce", "all-to-all", "collective-permute",
                "reduce-scatter"} & set(opcodes)


def test_isolet_ae_pretraining_compiles_for_v5e_with_kernel_body(
        one_chip, monkeypatch):
    """Hidden layer 1's pretraining step of ``isolet_ae`` at batch 64:
    the backward program of the modelled chip that holds the frozen
    encoder 0 and encoder 1 (training from stage 1), and the serial
    chip's whole step with the autoencoder target; both donate their
    stacks."""
    from repro.core import hw_model as hw
    from repro.sim import ChipPipeline
    monkeypatch.setattr(kernel_ops, "_default_interpret", lambda: False)
    dims = hw.PAPER_NETWORKS["isolet_ae"]
    key = jax.random.PRNGKey(0)
    pairs = list(zip(dims, dims[1:]))
    enc = [xb.init_conductances(jax.random.fold_in(key, i), f, o,
                                PAPER_SPEC) for i, (f, o) in enumerate(pairs)]
    dec = [xb.init_conductances(jax.random.fold_in(key, 9 + i), o, f,
                                PAPER_SPEC) for i, (f, o) in enumerate(pairs)]
    s = one_chip
    path = ChipPipeline(enc, PAPER_SPEC, n_chips=2,
                        decoders=dec)._pretrain_path(1)
    assert (path.groups, path._owner(1)) == (((0, 1), (2,)), (0, 1))
    chip = VirtualChip(enc, PAPER_SPEC, decoders=dec)
    for st, cfg, program, data in [
            (path.chips[0]._get_stacks(), path.chips[0]._cfg,
             csim.chip_backward, "wave"),
            (chip._pretrain_path(1)._get_stacks(),
             chip._pretrain_path(1)._cfg, csim.chip_train, "input")]:
        cfg = cfg._replace(use_kernels=True)
        idx = {k: _spec(s, *v.shape, dtype=v.dtype)
               for k, v in st.index_pytree().items()}
        g = (_spec(s, *st.g_plus.shape), _spec(s, *st.g_minus.shape))
        if data == "wave":
            args = (_spec(s, st.S, 64, st.L), _spec(s, st.S, 64, st.N_pad),
                    _spec(s, 64, st.N_pad))
        else:
            args = (_spec(s, 64, dims[0]), None)
        compiled = program.lower(*g, *args, idx, cfg, lr_eff=_spec(s),
                                 first=1).compile()
        assert "tpu_custom_call" in compiled.as_text()
        assert compiled.memory_analysis().alias_size_in_bytes \
            >= 2 * st.g_plus.nbytes


#: sha256 of the lowered text of `chip_infer` for ``mnist_class`` at 1024
#: rows on one v5e, kernel body, source locations left out: the program
#: of ``mnist_class.chip_infer.b1024``.  A change to it is a change to
#: that cell's program, and moves this digest on purpose.
CHIP_INFER_MNIST_1024 = (
    "e23d17bffae91a2d774ad2a56a5d7740166b769f7562aacbac6ccd55114d19dc")


def test_mnist_chip_infer_lowers_to_its_pinned_program(one_chip,
                                                       monkeypatch):
    """The recognition program at 1024 rows lowers to the text it had
    when pinned: serving's programs share its body without moving it."""
    import hashlib
    monkeypatch.setattr(kernel_ops, "_default_interpret", lambda: False)
    dims = NETWORKS["mnist_class"]
    key = jax.random.PRNGKey(0)
    layers = [xb.init_conductances(jax.random.fold_in(key, i), f, o,
                                   PAPER_SPEC)
              for i, (f, o) in enumerate(zip(dims, dims[1:]))]
    chip = VirtualChip(layers, PAPER_SPEC)
    st = chip._get_stacks()
    s = one_chip
    idx = {k: _spec(s, *v.shape, dtype=v.dtype)
           for k, v in st.index_pytree().items()}
    limit = jax.config.jax_traceback_in_locations_limit
    jax.config.update("jax_traceback_in_locations_limit", 0)
    try:
        text = csim.chip_infer.lower(
            _spec(s, *st.g_plus.shape), _spec(s, *st.g_minus.shape),
            _spec(s, 1024, dims[0]), idx,
            chip._cfg._replace(use_kernels=True)).as_text()
    finally:
        jax.config.update("jax_traceback_in_locations_limit", limit)
    assert "tpu_custom_call" in text
    assert hashlib.sha256(text.encode()).hexdigest() == CHIP_INFER_MNIST_1024
