"""Compile for a described TPU v5e, with no chip attached, what the GCN
path runs there: every Pallas kernel that ``--cache-probe-impl pallas``
or ``use_kernel`` can reach, at the ``graphgen-gcn`` widths, the W=1
pipelined cached step at ``chip_smoke.py``'s shapes, which must fit one
chip's 16 GiB, and the W=1 tiered generator at the GAT cell's shapes,
which must read each slot's feature row straight into the batch.

The topology is described only inside a fixture: describing it loads the
TPU compiler's library, which one process at a time may hold.  The
persistent compilation cache is off around these compiles, because
their entries could not be read back without a chip.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.configs import get_config
from repro.core.config import TrainConfig
from repro.core.feature_cache import (CacheConfig, FeatureCache,
                                      cache_insert, cache_state_specs)
from repro.core.generation import make_generator_fn, probe_round_capacity
from repro.core.pipeline import make_pipelined_step
from repro.graph.subgraph import batch_specs, slots_per_seed
from repro.kernels.cache_gather import (cache_probe_compact_pallas,
                                        cache_probe_gather_pallas,
                                        cache_probe_tiered_pallas)
from repro.kernels.fanout_mean import fanout_mean_pallas
from repro.models import gcn as gcn_mod
from repro.train.optimizer import adam_update, init_adam

CFG = get_config("graphgen-gcn")
#: chip_smoke.py's W=1 workload: ogbn-products' node count and the edge
#: count its synthetic graph gets at average degree 25, 1024 seeds
NODES = 2_449_029
EDGES = 59_548_242
BATCH = 1024
HBM_BYTES = 16 * 1024 ** 3          # one v5e chip
SLACK = 2.0


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # any failure to describe the chip means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_compile_cache):
    return SingleDeviceSharding(topo.devices[0])


def _kernel_case(name):
    """``(fn, [(shape, dtype), ...])`` of one kernel at the config's widths
    and at the request counts of the smoke's W=1 and W=4 steps."""
    cc = CacheConfig.from_model(CFG)
    c, a, d = cc.n_rows, cc.assoc, CFG.gcn_in_dim
    c1 = c // 8                                # tiered mode's auto L1
    a1 = cc._replace(mode="tiered", l1_rows=c1).l1_assoc
    r = BATCH * slots_per_seed(CFG.fanouts)
    cap = probe_round_capacity(256 * slots_per_seed(CFG.fanouts), 4, SLACK)
    k1, k2 = CFG.fanouts
    i32, f32 = jnp.int32, jnp.float32
    cases = {
        "gather": (functools.partial(cache_probe_gather_pallas, assoc=a,
                                     interpret=False),
                   [((c,), i32), ((c, d), f32), ((r,), i32)]),
        "tiered": (functools.partial(cache_probe_tiered_pallas, l1_assoc=a1,
                                     l2_assoc=a, interpret=False),
                   [((c1,), i32), ((c1, d), f32), ((c,), i32), ((c, d), f32),
                    ((r,), i32)]),
        "compact": (functools.partial(cache_probe_compact_pallas, assoc=a,
                                      hit_cap=cap // 2, interpret=False),
                    [((c,), i32), ((c, d), f32), ((4, cap), i32)]),
        # the GCN's two aggregations, on rows laid out as the tree levels are
        "fanout_mean_hop2": (
            lambda x, m: fanout_mean_pallas(
                x.reshape(BATCH * k1, k2, d), m, interpret=False),
            [((BATCH * k1 * k2, d), f32), ((BATCH * k1, k2), jnp.bool_)]),
        "fanout_mean_hidden": (
            lambda x, m: fanout_mean_pallas(
                x.reshape(BATCH, k1, CFG.gcn_hidden), m, interpret=False),
            [((BATCH * k1, CFG.gcn_hidden), f32), ((BATCH, k1), jnp.bool_)]),
    }
    return cases[name]


@pytest.mark.parametrize("name", ["gather", "tiered", "compact",
                                  "fanout_mean_hop2", "fanout_mean_hidden"])
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = _kernel_case(name)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo, f"{name}: no Mosaic kernel in the HLO"


@pytest.mark.parametrize("case", ["sharded", "tiered"])
def test_stage_scopes_are_metadata_on_v5e(case, topo, no_compile_cache):
    """The generator of each W=1 cell's tier, compiled by the chip's
    compiler: every timed op under ``jit(gen_fn)`` sits in a stage, and
    without the scopes the program is the same instruction for
    instruction."""
    import _stage_scopes
    rep = _stage_scopes.report(case, devices=topo.devices)
    assert not rep["unstaged"], rep["unstaged"]
    assert rep["same_unscoped"]
    assert _stage_scopes.expected_stages(case, 1) <= set(rep["stages"])


def test_cache_insert_resolves_per_slot_on_v5e(one_chip):
    """The insert at the 2-hop cell's shapes (861,184 offers into 4096
    four-way slots of 128 floats), compiled by the chip's compiler, has
    no ``while`` loop (a binary search over the offers) and no scatter
    whose updates carry a row of D per offer (an R x D write)."""
    r, c, d = 861_184, 4096, 128
    cfg = CacheConfig(c, admit=2, assoc=4, mode="sharded")

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    state = FeatureCache(spec((c,), jnp.int32), spec((c, d), jnp.float32),
                         spec((c,), jnp.int32), spec((c,), jnp.int32))
    text = jax.jit(lambda s, i, x, m: cache_insert(s, i, x, m, cfg)).lower(
        state, spec((r,), jnp.int32), spec((r, d), jnp.float32),
        spec((r,), jnp.bool_)).compile().as_text()
    assert not re.search(r"\swhile\(", text), "a while loop over the offers"
    # instruction names are unique in the module: a scatter's updates
    # operand is defined, with its shape, on a line of its own
    shapes = dict(re.findall(r"%([\w.\-]+) = \w+\[([\d,]*)\]", text))
    scatters = re.findall(r"\sscatter\(%[\w.\-]+, %[\w.\-]+, %([\w.\-]+)\)",
                          text)
    assert scatters, "no scatter found: the HLO text changed form"
    for updates in scatters:
        n = np.prod([int(k) for k in shapes[updates].split(",") if k])
        assert n < r * d, f"scatter of {shapes[updates]} updates"


def _with_sharding(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def test_gcn_step_fits_one_v5e(topo, no_compile_cache):
    """The pipelined cached step (generation of t+1 fused with training of
    t) that ``train_gcn`` runs at W=1 compiles for one chip and fits it."""
    mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))
    shard, repl = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    cache_cfg = CacheConfig.from_model(CFG)
    gen_fn = make_generator_fn(mesh, fanouts=CFG.fanouts,
                               capacity_slack=SLACK, cache_cfg=cache_cfg)
    tcfg = TrainConfig()

    def train_fn(params, opt, batch):
        loss, grads = jax.value_and_grad(gcn_mod.gcn_loss)(params, batch)
        params, opt, _ = adam_update(tcfg, params, grads, opt)
        return params, opt, loss

    params = jax.eval_shape(
        lambda: gcn_mod.init_gcn(CFG, jax.random.PRNGKey(0)))
    opt = jax.eval_shape(lambda: init_adam(params))
    d, s, i32 = CFG.gcn_in_dim, jax.ShapeDtypeStruct, jnp.int32
    carry = (_with_sharding(params, repl), _with_sharding(opt, repl),
             _with_sharding(batch_specs(BATCH, CFG.fanouts, d), shard),
             _with_sharding(cache_state_specs(cache_cfg, d), shard))
    device_args = _with_sharding(
        (s((1, NODES + 1), i32), s((1, EDGES), i32),
         s((NODES, d), jnp.float32), s((NODES, 1), jnp.float32)), shard)
    seeds = s((1, BATCH), i32, sharding=shard)
    rng = _with_sharding(jax.eval_shape(lambda: jax.random.PRNGKey(0)), repl)
    step = jax.jit(make_pipelined_step(gen_fn, train_fn, cached=True))
    mem = step.lower(carry, device_args, seeds, rng).compile() \
        .memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < HBM_BYTES, f"step needs {used / 2**30:.2f} GiB of 16"


#: the ``gat-rmat20-w1`` cell's generator: R-MAT scale 20, 1024-wide rows,
#: 512 seeds of fanouts (15, 10, 5), a 512-row L1 before a 4096-row L2
GAT_NODES, GAT_EDGES, GAT_DIM, GAT_BATCH = 2 ** 20, 33_554_432, 1024, 512
GAT_FANOUTS = (15, 10, 5)
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
_HLO_OP = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(\w+)\[([\d,]*)\]"
                     r"\S*\s+([\w\-]+)\(")


def _hlo_ops(text):
    """``(computation, name, dims, opcode, op_name, dtype)`` of every
    instruction whose result is one array, and each computation's
    opcodes."""
    ops, bodies, comp = [], {}, None
    for line in text.splitlines():
        head = _HLO_COMPUTATION.match(line)
        if head:
            comp = head.group(1)
            continue
        m = _HLO_OP.match(line)
        if not m:
            continue
        dims = tuple(int(k) for k in m.group(3).split(",") if k)
        op_name = re.search(r'op_name="([^"]*)"', line)
        ops.append((comp, m.group(1), dims, m.group(4),
                    op_name.group(1) if op_name else "", m.group(2)))
        bodies.setdefault(comp, set()).add(m.group(4))
    return ops, bodies


def _assert_w1_fetch_reads_rows_once(topo, nodes, edges, d, batch,
                                     fanouts, cfg, temp_max):
    """Compile a W=1 cached generator by the chip's compiler and assert
    it builds no [R, D] buffer of rows: the probe returns no rows, every
    slot's row is gathered from the table and from the cache rows and
    picked by one select per tree level, written in the batch's own
    layout (no multiply, copy, transpose, reshape or slice of rows
    anywhere), and the temporaries stay under ``temp_max`` bytes."""
    r = batch * slots_per_seed(fanouts)
    mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))
    shard = NamedSharding(mesh, P("data"))
    cfg = cfg.validated()

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=shard)
    device_args = (spec((1, nodes + 1), jnp.int32),
                   spec((1, edges), jnp.int32),
                   spec((nodes, d), jnp.float32),
                   spec((nodes, 1), jnp.float32))
    cache = _with_sharding(cache_state_specs(cfg, d), shard)
    gen_fn = make_generator_fn(mesh, fanouts=fanouts, capacity_slack=SLACK,
                               cache_cfg=cfg)
    compiled = jax.jit(gen_fn).lower(
        device_args, spec((1, batch), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.uint32), cache).compile()
    text = compiled.as_text()
    ops, bodies = _hlo_ops(text)
    calls = dict(re.findall(
        r"%([\w.\-]+) = [^\n]*?\sfusion\([^\n]*?calls=%([\w.\-]+)", text))
    # instructions the device runs: those outside fused computations
    # that move data (a bitcast renames a buffer)
    top = [o for o in ops if o[0] not in set(calls.values())
           and o[3] not in ("bitcast", "parameter", "get-tuple-element")]
    # arrays of rows that scale with the slots: more than the caches hold
    # (a relayout copy the compiler adds may carry no op_name)
    rows = [o for o in top if o[5] == "f32" and len(o[2]) >= 2
            and o[2][-1] == d
            and np.prod(o[2][:-1]) > cfg.n_rows + cfg.l1_rows]
    assert [o for o in rows if "jit(gen_fn)" in o[4]], \
        "no row-sized instruction found: the HLO text changed form"

    def body(o):
        return bodies.get(calls.get(o[1]), {o[3]})
    # a gather fusion's body holds the bitcast reshapes and identity
    # transposes of its output; anything else that moves rows is a pass
    moves = {"multiply", "copy", "reshape", "slice", "transpose"}
    assert not [o[1:4] for o in rows
                if moves & body(o) and "gather" not in body(o)], \
        "rows multiplied, copied, transposed, reshaped or sliced"
    assert not [o for o in rows if np.prod(o[2][:-1]) == r], \
        "a buffer of one row per request slot or distinct id"
    assert not [o for o in rows if "/cache_probe/" in o[4]], \
        "the probe returns rows"
    picks = [o for o in rows if "/slot_scatter/" in o[4]
             and "gather" not in body(o)]
    assert all(o[3] == "fusion" and "select" in body(o) for o in picks), \
        [o[1:4] for o in picks]
    assert sum(np.prod(o[2][:-1]) for o in picks) <= r, \
        "more than one select pass over the slots"
    assert sum(np.prod(o[2][:-1]) for o in rows
               if "gather" in body(o)) <= 2 * r, \
        "more than two rows gathered per slot"
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < temp_max, f"{temp} B of temporaries"


def test_w1_cached_fetch_gathers_rows_once_on_v5e(topo, no_compile_cache):
    """The W=1 tiered generator at the GAT cell's shapes (468,992 request
    slots of 1024 floats) reads each slot's rows straight into the batch
    (``_assert_w1_fetch_reads_rows_once``), and its temporaries fit in
    3 GB (5,777,522,176 B when the fetch built rows per distinct id)."""
    _assert_w1_fetch_reads_rows_once(
        topo, GAT_NODES, GAT_EDGES, GAT_DIM, GAT_BATCH, GAT_FANOUTS,
        CacheConfig(4096, admit=2, assoc=4, mode="tiered", l1_rows=512,
                    l1_promote=3, wire="compact"), temp_max=3.0e9)


#: the GCN cells' generators: R-MAT scale 23, 128-wide rows, 1024 seeds
GCN_NODES, GCN_EDGES = 2 ** 23, 268_435_456


@pytest.mark.parametrize("fanouts,cfg", [
    ((40, 20), CacheConfig(4096, admit=2, assoc=4, mode="sharded",
                           wire="compact")),                # gcn-rmat23-w1
    ((15, 10, 5), CacheConfig(4096, admit=2, assoc=4, mode="tiered",
                              l1_rows=512, l1_promote=3,
                              wire="compact")),             # gcn-deep-...
], ids=["gcn-rmat23-w1", "gcn-deep-rmat23-w1"])
def test_w1_gcn_generators_read_rows_once_on_v5e(fanouts, cfg, topo,
                                                 no_compile_cache):
    """The same at the GCN cells' shapes, whose levels ``_tiled_order``
    lays out otherwise than the GAT's; their temporaries fit in 0.9 GB
    (1,347,838,976 and 1,469,897,728 B when the fetch built rows per
    distinct id)."""
    _assert_w1_fetch_reads_rows_once(topo, GCN_NODES, GCN_EDGES, 128, 1024,
                                     fanouts, cfg, temp_max=0.9e9)


@pytest.mark.parametrize("shape", [
    (1024, 40, 128), (1024, 40, 20, 128),                  # gcn-rmat23-w1
    (1024, 15, 128), (1024, 15, 10, 128), (1024, 15, 10, 5, 128),
    (512, 15, 1024), (512, 15, 10, 1024), (512, 15, 10, 5, 1024),
])
def test_tiled_order_is_the_v5e_default_layout(shape, one_chip):
    """The W=1 fetch gathers a tree level's rows in ``_tiled_order``, so
    that giving them the level's shape moves no data; that holds where
    the order is the chip's default layout of the level's rows, the
    layout the batch leaves the step in."""
    from repro.core.generation import _tiled_order
    text = jax.jit(lambda x: x + 1).lower(
        jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    ).compile().as_text()
    layout = re.search(r"->f32\[[\d,]+\]\{([\d,]+)", text).group(1)
    minor_to_major = tuple(int(k) for k in layout.split(","))
    order = _tiled_order(shape[:-1]) + (len(shape) - 1,)
    assert minor_to_major == tuple(reversed(order))
