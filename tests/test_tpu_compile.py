"""Compile for a described TPU v5e, with no chip attached, what the GCN
path runs there: every Pallas kernel that ``--cache-probe-impl pallas``
or ``use_kernel`` can reach, at the ``graphgen-gcn`` widths, and the W=1
pipelined cached step at ``chip_smoke.py``'s shapes, which must fit one
chip's 16 GiB.

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
