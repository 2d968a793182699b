"""The generator's stage scopes as the compiled program carries them.

Compiles the generator of a tiny R-MAT-shaped configuration (the
benchmark cells' widths cut down: 16-wide features, fanouts (4, 3),
64-row caches) and reads each instruction's JAX name stack (``op_name``)
from the optimized HLO text.  Run as a script with
``--xla_force_host_platform_device_count`` set, it prints the report of
every case at that many workers as one JSON line, for tests that need
more devices than their own process has.
"""
import contextlib
import json
import re
import sys
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import generation
from repro.core.feature_cache import CacheConfig, cache_state_specs
from repro.core.host_store import empty_admit

DIM = 16
FANOUTS = (4, 3)
NODES, EDGES, SEEDS = 1024, 16384, 8
#: (cache mode or None, feature store, merge): the paths ``fetch_rows``
#: and the tree merge take
CASES = {
    "uncached": (None, "device", "butterfly"),
    "replicated": ("replicated", "device", "butterfly"),
    "sharded": ("sharded", "device", "butterfly"),
    "tiered": ("tiered", "device", "butterfly"),
    "host": (None, "host", "butterfly"),
    "host-tiered": ("tiered", "host", "butterfly"),
    "reduce-scatter": ("sharded", "device", "reduce_scatter"),
}
#: instructions that take device time: every one under ``jit(gen_fn)``
#: must sit in a stage
TIMED = ("fusion", "gather", "scatter", "sort", "while", "all-to-all",
         "all-gather", "all-reduce", "collective-permute", "reduce-scatter")

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*.*?\s([\w\-]+)\((.*?)\)'
                    r'.*?metadata=\{[^}]*?op_name="([^"]*)"')
_CONSTANT = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*\S+\s+constant\(",
                       re.M)


def compiled_text(case: str, workers: int = 1, scoped: bool = True,
                  devices=None) -> str:
    """Optimized HLO text of the jitted generator of ``case`` on the first
    ``workers`` of ``devices`` (default: JAX's); ``scoped=False`` compiles
    it with every stage scope replaced by a no-op."""
    mode, store, merge = CASES[case]
    cfg = None
    if mode is not None:
        cfg = CacheConfig(n_rows=64, admit=2, assoc=4, mode=mode,
                          l1_rows=16 if mode == "tiered" else 0,
                          store=store).validated()
    devices = jax.devices() if devices is None else devices
    mesh = Mesh(np.asarray(devices[:workers]), ("data",))
    shard = NamedSharding(mesh, P("data"))

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=shard)
    rows = -(-NODES // workers)
    graph = (spec((workers, NODES + 1), jnp.int32),
             spec((workers, EDGES // workers), jnp.int32))
    y = spec((rows * workers, 1), jnp.float32)
    device_args = (graph + (y,) if store == "host" else
                   graph + (spec((rows * workers, DIM), jnp.float32), y))
    args = [device_args, spec((workers, SEEDS), jnp.int32),
            jax.ShapeDtypeStruct((2,), jnp.uint32)]
    if cfg is not None:
        args.append(jax.tree.map(lambda s: spec(s.shape, s.dtype),
                                 cache_state_specs(cfg, DIM, workers)))
        if store == "host":
            admit = jax.eval_shape(lambda: empty_admit(workers, DIM))
            args += [spec(a.shape, a.dtype) for a in admit]
    gen_fn = generation.make_generator_fn(
        mesh, fanouts=FANOUTS, merge_mode=merge, cache_cfg=cfg,
        feature_store=store,
        feat_dim=DIM if store == "host" else None)
    off = (mock.patch.object(generation, "_stage",
                             lambda name: contextlib.nullcontext())
           if not scoped else contextlib.nullcontext())
    with off:
        lowered = jax.jit(gen_fn).lower(*args)
    return lowered.compile().as_text()


def stage_of(op_name: str):
    """The outermost generation stage in a JAX name stack, transform
    wrappers such as ``vmap(...)`` unwrapped; None where there is none."""
    for part in op_name.split("/"):
        while (m := re.fullmatch(r"\w+\((.*)\)", part)):
            part = m.group(1)
        if part in generation.STAGES:
            return part
    return None


def generator_ops(text: str):
    """``(name, opcode, op_name)`` of the instructions under
    ``jit(gen_fn)`` that can appear in a device trace: those outside fused
    computations, less the ones computed from constants alone (the
    compiler's materialized constants, which name no JAX operation)."""
    fused = set(re.findall(r"\sfusion\(.*?calls=%([\w.\-]+)", text))
    constants = set(_CONSTANT.findall(text))
    ops, comp = [], None
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            comp = head.group(1)
            continue
        m = _INSTR.match(line)
        if comp in fused or not m or "jit(gen_fn)" not in m.group(4):
            continue
        operands = re.findall(r"%([\w.\-]+)", m.group(3))
        if all(o in constants for o in operands):
            continue
        ops.append((m.group(1), m.group(2), m.group(4)))
    return ops


def canonical(text: str) -> str:
    """HLO text without metadata or source tables, with every instruction
    and computation renamed by order of first appearance."""
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
    head, _, tables = text.partition("\n\nFileNames\n")
    if tables:
        text = head + "\n" + tables[tables.index("\n\n%"):]
    names = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: names.setdefault(m.group(0), f"%v{len(names)}"),
                  text)


def report(case: str, workers: int = 1, devices=None) -> dict:
    """The stages present in ``case``'s compiled generator, its timed
    instructions that lack one, and whether the program compiled without
    the scopes is the same instruction for instruction."""
    text = compiled_text(case, workers, devices=devices)
    ops = generator_ops(text)
    return {
        "stages": sorted({s for s in map(stage_of, (o[2] for o in ops))
                          if s is not None}),
        "unstaged": [o for o in ops
                     if o[1].startswith(TIMED) and stage_of(o[2]) is None],
        "same_unscoped": canonical(text) == canonical(
            compiled_text(case, workers, scoped=False, devices=devices)),
    }


def expected_stages(case: str, workers: int) -> set:
    """The stages with work in ``case`` at ``workers`` workers."""
    want = {"edge_scan", "tree_merge", "dedup", "owner_fetch",
            "slot_scatter", "labels"}
    if CASES[case][0] is not None:
        want |= {"cache_probe", "cache_insert"}
    if workers > 1:
        want.add("frontier")
    return want


if __name__ == "__main__":
    n = jax.device_count()
    print(json.dumps({c: report(c, n) for c in sys.argv[1:] or CASES}))
