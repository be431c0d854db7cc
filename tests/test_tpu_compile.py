"""Compile rehearsals of the round kernels for a TPU v5e.

Each case lowers and compiles one Pallas round kernel for a v5e chip
that is described, not attached: the chip's kernel compiler (Mosaic)
refuses here what interpret mode cannot see — blocks that do not tile,
more VMEM than a kernel may use. Shapes are those of the paper
federation (MLP 784-10-10-10, K=100, raveled d=8070 and its largest
pytree leaf), the compressed int8 cohort (m=32, s=d/16), smollm-135m
client leaves at K=4 in bf16 (flattened, and in their own shapes, which
the kernels read in their own layout), and a cohort plane of m=256,
d=16384.

The topology is described inside a module fixture: describing it loads
the TPU library, which one process at a time may hold, so nothing here
touches it while modules are imported.

Whole programs are compiled too: the one-round advance of a small
paper federation, whose data plane must stay out of a cross-program
prefetch (``repro.fl.fused.TPU_SCAN_OPTIONS``), the scan of a
two-layer federation of smollm-width clients, whose kernel operands
must reach the kernels without relayout loops, and the scan of a
paper-shaped compressed cohort, whose slots must gather their
minibatches without relaying out the data plane.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.aircomp_sum import (gather_superpose_pallas,
                                       superpose_normalize_pallas)
from repro.kernels.round_stats import round_stats_pallas

F32, BF16 = jnp.float32, jnp.bfloat16

# (K, d, payload dtype): rows x flattened leaf width
PLANES = {
    "paper_raveled": (100, 8070, F32),
    "paper_leaf_784x10": (100, 7840, F32),
    "smollm_embed_k4": (4, 49152 * 576, BF16),
    "smollm_mlp_k4": (4, 576 * 1536, BF16),
    "cohort_m256": (256, 16384, F32),
    "large_k1000": (1000, 8070, F32),
}
# smollm-135m leaves at K=4 in bf16, in their own shapes: the MLP's down
# projection, the embedding, the K/V projections, the layers' norm scales
# and the final norm
LEAVES = {
    "mlp_down": (4, 30, 1536, 576),
    "embedding": (4, 49152, 576),
    "attn_wk": (4, 30, 576, 192),
    "layer_norms": (4, 30, 576),
    "final_norm": (4, 576),
}
# (m, d, s, value dtype, int8 scale)
COMPRESSED = {
    "paper_randmask16_int8": (32, 8070, 504, jnp.int8, True),
    "cohort_m256_int8": (256, 16384, 1024, jnp.int8, True),
    "cohort_m256_f32": (256, 16384, 1024, F32, False),
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                   # no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype=F32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_kernels(fn, *args) -> int:
    """Compile ``fn`` for the described chip; the count of Mosaic kernels
    in the compiled program."""
    return jax.jit(fn).lower(*args).compile().as_text().count(
        "tpu_custom_call")


@pytest.mark.parametrize("payload", [False, True], ids=["delta", "model"])
@pytest.mark.parametrize("plane", list(PLANES))
def test_round_stats_compiles(one_chip, plane, payload):
    k, d, dt = PLANES[plane]
    args = [_spec(one_chip, (k, d), dt), _spec(one_chip, (d,))]
    if payload:
        args.append(_spec(one_chip, (k, d), dt))
    assert _compiled_kernels(
        lambda de, g, *p: round_stats_pallas(de, g, *p), *args) == 1


@pytest.mark.parametrize("plane", list(PLANES))
def test_superpose_normalize_compiles(one_chip, plane):
    k, d, dt = PLANES[plane]
    assert _compiled_kernels(
        superpose_normalize_pallas, _spec(one_chip, (k, d), dt),
        _spec(one_chip, (k,)), _spec(one_chip, (k,)),
        _spec(one_chip, (d,))) == 1


@pytest.mark.parametrize("payload", [False, True], ids=["delta", "model"])
@pytest.mark.parametrize("leaf", list(LEAVES))
def test_round_stats_compiles_native_leaf(one_chip, leaf, payload):
    shape = LEAVES[leaf]
    args = [_spec(one_chip, shape, BF16), _spec(one_chip, shape[1:])]
    if payload:
        args.append(_spec(one_chip, shape, BF16))
    assert _compiled_kernels(
        lambda de, g, *p: round_stats_pallas(de, g, *p), *args) == 1


@pytest.mark.parametrize("leaf", list(LEAVES))
def test_superpose_normalize_compiles_native_leaf(one_chip, leaf):
    shape = LEAVES[leaf]
    assert _compiled_kernels(
        superpose_normalize_pallas, _spec(one_chip, shape, BF16),
        _spec(one_chip, shape[:1]), _spec(one_chip, shape[:1]),
        _spec(one_chip, shape[1:])) == 1


@pytest.mark.parametrize("case", list(COMPRESSED))
def test_gather_superpose_compiles(one_chip, case):
    m, d, s, vdt, scaled = COMPRESSED[case]
    args = [_spec(one_chip, (m, s), vdt), _spec(one_chip, (m, s), jnp.int32),
            _spec(one_chip, (m,)), _spec(one_chip, (d,))]
    if scaled:
        args.append(_spec(one_chip, (m,)))
    assert _compiled_kernels(
        lambda v, i, bp, n, *sc: gather_superpose_pallas(
            v, i, bp, n, d=d, scale=sc[0] if sc else None), *args) == 1


def test_one_round_scan_has_no_cross_program_prefetch(one_chip):
    """A one-round scan compiles without its while loop, and the TPU
    compiler would then prefetch the (K, n, 784) data plane into VMEM
    across programs; that program hung on a v5e. Under the options the
    driver compiles with on a TPU, the plane stays in HBM and the round
    kernels are still in the program."""
    from repro.core import ChannelConfig, SchedulerConfig
    from repro.data.partition import partition_noniid
    from repro.data.pipeline import build_federation
    from repro.data.synthetic import make_mnist_like
    from repro.fl import FLClient, FusedPAOTA, PAOTAConfig
    from repro.fl.fused import TPU_SCAN_OPTIONS
    from repro.models.mlp import init_mlp_params, mlp_loss

    k = 8
    x, y, _, _ = make_mnist_like(n_train=400, n_test=10)
    clients = [FLClient(d, mlp_loss, batch_size=32, lr=0.1, local_steps=2)
               for d in build_federation(x, y, partition_noniid(
                   y, n_clients=k, seed=0))]
    srv = FusedPAOTA(init_mlp_params(jax.random.PRNGKey(0)), clients,
                     ChannelConfig(), SchedulerConfig(n_clients=k, seed=1),
                     PAOTAConfig())
    put = lambda t: jax.tree_util.tree_map(
        lambda a: _spec(one_chip, a.shape, a.dtype), t)
    carry = jax.eval_shape(srv._init_carry, srv._init_global,
                           srv.engine._x, srv.engine._y)
    text = srv._jit_scan.lower(
        put(carry), put(srv.engine._x), put(srv.engine._y),
        n_rounds=1).compile(compiler_options=TPU_SCAN_OPTIONS).as_text()
    assert "cross_program_prefetch_index" not in text
    assert text.count("tpu_custom_call") > 0


KERNEL_STAGES = {
    # driver options: (kernel instruction, the stage scope it runs under)
    "dense": ({}, [("round_stats_pallas", "paota.stats"),
                   ("superpose_normalize_pallas", "paota.superpose")]),
    "cohort_randmask_int8": (
        dict(cohort_size=4, compress="randmask", compress_ratio=1 / 16,
             slot_dtype="int8"),
        [("gather_superpose_pallas", "paota.superpose")]),
}


@pytest.mark.parametrize("case", list(KERNEL_STAGES))
def test_round_kernels_sit_under_their_stage_scopes(one_chip, case):
    """In the two-round scan compiled for the chip, each round kernel's
    custom call is named after the kernel and carries, in its ``op_name``,
    the stage scope it runs under and the kernel's own name: the trace
    readers find kernels and stages by these names."""
    import re

    from repro.core import ChannelConfig, SchedulerConfig
    from repro.data.partition import partition_noniid
    from repro.data.pipeline import build_federation
    from repro.data.synthetic import make_mnist_like
    from repro.fl import FLClient, FusedPAOTA, PAOTAConfig
    from repro.fl.fused import TPU_SCAN_OPTIONS
    from repro.models.mlp import init_mlp_params, mlp_loss

    kw, kernels = KERNEL_STAGES[case]
    k = 8
    x, y, _, _ = make_mnist_like(n_train=400, n_test=10)
    clients = [FLClient(d, mlp_loss, batch_size=16, lr=0.1, local_steps=2)
               for d in build_federation(x, y, partition_noniid(
                   y, n_clients=k, seed=0))]
    srv = FusedPAOTA(init_mlp_params(jax.random.PRNGKey(0)), clients,
                     ChannelConfig(), SchedulerConfig(n_clients=k, seed=1),
                     PAOTAConfig(transmit="delta" if kw else "model"), **kw)
    put = lambda t: jax.tree_util.tree_map(
        lambda a: _spec(one_chip, a.shape, a.dtype), t)
    carry = jax.eval_shape(srv._init_carry, srv._init_global,
                           srv.engine._x, srv.engine._y)
    text = srv._jit_scan.lower(
        put(carry), put(srv.engine._x), put(srv.engine._y),
        n_rounds=2).compile(compiler_options=TPU_SCAN_OPTIONS).as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for kernel, scope in kernels:
        mine = [c for c in calls
                if re.match(rf"\s*(ROOT\s+)?%{kernel}(\.\d+)*\s*=", c)]
        assert mine, kernel
        for c in mine:
            op_name = re.search(r', metadata=\{[^}]*op_name="([^"]*)"',
                                c).group(1)
            # the kernel's own name (``pallas_call(name=...)``) inside
            # the stage
            assert f"/{scope}/" in op_name, op_name
            assert f"/{kernel}/pallas_call" in op_name, op_name


@pytest.fixture(scope="module")
def smollm_scan(one_chip):
    """The compiled HLO text of the scan of a two-layer federation of
    smollm-width clients (bf16 planes, a pytree carry, model transmit),
    compiled for the described chip, and the computations in it that run
    both round kernels."""
    import dataclasses
    import re

    import numpy as np

    from repro.configs.smollm_135m import CONFIG
    from repro.core import ChannelConfig, SchedulerConfig
    from repro.data.pipeline import ClientData
    from repro.fl import FLClient, FusedPAOTA, PAOTAConfig
    from repro.fl.fused import TPU_SCAN_OPTIONS
    from repro.models.transformer import init_model, loss_fn

    cfg = dataclasses.replace(CONFIG, num_layers=2, vocab_size=512)
    rng = np.random.default_rng(0)
    k = 4

    def loss(p, b):
        return loss_fn(p, {"tokens": b["x"]}, cfg)[0]

    clients = [FLClient(ClientData(
        rng.integers(0, cfg.vocab_size, (4, 32)).astype(np.int32),
        np.zeros(4, np.int32), i), loss, batch_size=2, lr=0.01,
        local_steps=1) for i in range(k)]
    srv = FusedPAOTA(init_model(jax.random.PRNGKey(0), cfg), clients,
                     ChannelConfig(), SchedulerConfig(n_clients=k, seed=0),
                     PAOTAConfig(), params_mode="pytree",
                     pending_dtype="bfloat16")
    put = lambda t: jax.tree_util.tree_map(
        lambda a: _spec(one_chip, a.shape, a.dtype), t)
    carry = jax.eval_shape(srv._init_carry, srv._init_global,
                           srv.engine._x, srv.engine._y)
    text = srv._jit_scan.lower(
        put(carry), put(srv.engine._x), put(srv.engine._y),
        n_rounds=2).compile(compiler_options=TPU_SCAN_OPTIONS).as_text()
    bodies = [c for c in re.split(r"\n(?=\S)", text)
              if "round_stats_pallas" in c
              and "superpose_normalize_pallas" in c]
    assert bodies
    return bodies


def test_scan_feeds_the_round_kernels_without_relayout_loops(smollm_scan):
    """In the scan of a two-layer federation of smollm-width clients
    (bf16 planes, a pytree carry, model transmit) compiled for the chip,
    the computation that runs the round kernels holds no ``while`` loop
    without an ``op_name``: the program's own loops (the water-filling
    search, the training's layer scans) carry the op_name of their
    source; the loops the compiler writes to relay a tiled leaf out into
    a flat (K, n) operand carry none. The kernels read each leaf in its
    own layout, so there are none of those."""
    import re

    for body in smollm_scan:
        loops = [line for line in body.splitlines()
                 if re.search(r"\) while\(|= \S+ while\(", line)]
        assert loops                      # the water-filling search
        unnamed = [line[:120] for line in loops if "op_name=" not in line]
        assert not unnamed, unnamed


def test_scan_draws_the_superposition_noise_in_each_leafs_layout(
        smollm_scan):
    """No operand of a superposition kernel in that scan comes out of a
    ``reshape``: in compiled TPU HLO a reshape that is left (not turned
    into a bitcast) is a relayout copy. The AWGN is drawn in each leaf's
    own shape; a flat draw's slices reshaped to the leaves would each be
    relaid out here."""
    import re

    inst = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = .*? ([\w\-]+)\((.*?)\)")
    through = ("bitcast", "get-tuple-element", "copy-start", "copy-done")
    for body in smollm_scan:
        defs = {}
        for line in body.splitlines():
            m = inst.match(line)
            if m:
                defs[m.group(1)] = (m.group(2),
                                    re.findall(r"%([\w.\-]+)", m.group(3)))
        calls = [n for n, (op, _) in defs.items() if op == "custom-call"
                 and n.startswith("superpose_normalize_pallas")]
        assert calls
        for call in calls:
            for x in defs[call][1]:
                while x in defs and defs[x][0] in through:
                    x = defs[x][1][0]
                assert x not in defs or defs[x][0] != "reshape", (call, x)


# the paper-shaped cohort: K clients of up to N samples of F features, m
# slots training M local steps of B samples
COHORT = dict(k=1000, n=300, f=784, m=64, steps=5, batch=32)


@pytest.fixture(scope="module")
def cohort_scan(one_chip):
    """The compiled HLO text of the 2-period scan of a paper-shaped
    compressed cohort (K=1000 MLP clients, a (1000, 300, 784) f32 data
    plane, 64 slots, M=5 steps of B=32, randmask 1/16, int8 slots,
    raveled, at HIGHEST), compiled for the described chip. The clients
    are built with four samples each: only the planes' shapes are the
    paper's."""
    import numpy as np

    from repro.core import ChannelConfig, SchedulerConfig
    from repro.data.pipeline import ClientData
    from repro.fl import FLClient, FusedPAOTA, PAOTAConfig
    from repro.fl.fused import TPU_SCAN_OPTIONS
    from repro.models.mlp import init_mlp_params, mlp_loss

    c = COHORT
    clients = [FLClient(ClientData(np.zeros((4, c["f"]), np.float32),
                                   np.zeros(4, np.int32), i), mlp_loss,
                        batch_size=c["batch"], lr=0.1,
                        local_steps=c["steps"]) for i in range(c["k"])]
    srv = FusedPAOTA(init_mlp_params(jax.random.PRNGKey(0)), clients,
                     ChannelConfig(),
                     SchedulerConfig(n_clients=c["k"], seed=1),
                     PAOTAConfig(transmit="delta"), cohort_size=c["m"],
                     compress="randmask", compress_ratio=1 / 16,
                     slot_dtype="int8")
    x = _spec(one_chip, (c["k"], c["n"], c["f"]))
    y = _spec(one_chip, (c["k"], c["n"]), jnp.int32)
    put = lambda t: jax.tree_util.tree_map(
        lambda a: _spec(one_chip, a.shape, a.dtype), t)
    with jax.default_matmul_precision("highest"):
        carry = jax.eval_shape(srv._init_carry, srv._init_global, x, y)
        return srv._jit_scan.lower(put(carry), x, y, n_rounds=2).compile(
            compiler_options=TPU_SCAN_OPTIONS).as_text()


def _loop_computations(text):
    """Name -> text of every computation a ``while`` loop of the compiled
    HLO ``text`` runs: the loops' bodies and conditions and all that they
    call, however deep."""
    import re

    comps = {}
    for block in re.split(r"\n(?=\S)", text):
        m = re.match(r"(?:ENTRY )?%([\w.\-]+) ", block)
        if m:
            comps[m.group(1)] = block
    refs = r"(?:calls|to_apply|body|condition)=%([\w.\-]+)"
    todo = re.findall(r"while\(.*?body=%([\w.\-]+)", text)
    seen = {}
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen[name] = comps[name]
        todo += re.findall(refs, comps[name])
        for group in re.findall(r"branch_computations=\{([^}]*)\}",
                                comps[name]):
            todo += re.findall(r"%([\w.\-]+)", group)
    return seen


def test_cohort_gathers_minibatches_without_relaying_the_plane(
        cohort_scan, smollm_scan):
    """In the paper-shaped cohort's scan, no instruction that the loop
    runs, but a parameter and its pass-through (get-tuple-element), has
    the data plane's (K, N, 784) shape, nor a (K, N, <784) slice of it:
    no period copies the plane into another layout or slices it row by
    row. The slots' minibatches come out of one gather of m x M x B
    rows. Outside the loop, the plane is relaid out at most once per
    call. The dense smollm scan, whose training keeps its per-step
    gather, still holds no relayout loop."""
    import re

    c = COHORT
    plane = re.compile(rf"^\s*(?:ROOT )?%\S+ = f32\[{c['k']},{c['n']},(\d+)\]"
                       rf"\S* ([\w\-]+)\(")
    passes = ("parameter", "get-tuple-element")
    loop = _loop_computations(cohort_scan)
    assert loop
    bad = []
    for body in loop.values():
        for line in body.splitlines():
            m = plane.match(line)
            if m and (m.group(2) not in passes or int(m.group(1)) != c["f"]):
                bad.append(line.strip()[:160])
    assert not bad, bad
    made = [m.group(2) for m in map(plane.match, cohort_scan.splitlines())
            if m and m.group(2) not in passes]
    assert len(made) <= 1, made
    rows = c["m"] * c["steps"] * c["batch"]
    gathers = [line for body in loop.values() for line in body.splitlines()
               if re.match(rf"^\s*(?:ROOT )?%\S+ = f32\[{rows},{c['f']}\]",
                           line) and "paota.train/gather" in line]
    assert gathers
    for body in smollm_scan:
        loops = [line for line in body.splitlines()
                 if re.search(r"\) while\(|= \S+ while\(", line)]
        assert not [line for line in loops if "op_name=" not in line]
