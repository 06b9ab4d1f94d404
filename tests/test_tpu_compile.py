"""The serve path's kernels compiled at the benchmark's real widths for a
DESCRIBED TPU v5e (no chip attached, nothing runs): what the chip's
compiler makes of a program is checked here at no chip time.

All such compiles live in this one file (one pytest-xdist worker loads the
TPU's library; a second file could land on another worker and skip). The
topology is described inside a fixture, never at import.
"""

import re
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from kmlserver_tpu.ops import embed as embed_ops
from kmlserver_tpu.ops.serve import (
    merge_partial_topk,
    recommend_batch,
    shard_partial_topk,
    sharded_recommend_fn,
)

# benchmark/configs/mpd-hybrid.json: the Million Playlist Dataset's catalog
V, RANK, K_BEST = 2262292, 32, 10
# benchmark/configs/yambda-rules-sharded.json: Yambda-5B's catalog, four ways
V_SHARDED, K_MAX, CHIP_LIMIT = 9390000, 256, 16909336064
# the rule epilogue's temporaries at any batch: it ranks candidate lanes,
# not a (B, V+1) score array (2.4 GB at (32, 128) on the sharded catalog)
EPILOGUE_TEMP_LIMIT = 16 << 20


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described device is written to the persistent cache
    and cannot be read back without a chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _widest_dims(hlo: str) -> list[int]:
    """Compiled HLO text → for each instruction line, the largest dimension
    of any array shape it names (result or operand)."""
    out = []
    for line in hlo.splitlines():
        if " = " not in line:
            continue
        dims = [
            int(d)
            for m in re.finditer(r"\b[a-z]+\d*\[([\d,]+)\]", line)
            for d in m.group(1).split(",")
            if d
        ]
        if dims:
            out.append(max(dims))
    return out


def _results(hlo: str) -> list[tuple[str, list[int]]]:
    """Compiled HLO text → (opcode, dimensions of its result shape) for
    each instruction line."""
    out = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (\([^)]*\)|\S+) ([\w\-]+)\(", line)
        if m:
            dims = [
                int(d)
                for shape in re.finditer(r"\b[a-z]+\d*\[([\d,]+)\]", m.group(1))
                for d in shape.group(1).split(",")
                if d
            ]
            out.append((m.group(2), dims))
    return out


def _computations(hlo: str) -> dict[str, list[str]]:
    """Compiled HLO text → {computation name: its instruction lines}."""
    out: dict[str, list[str]] = {}
    name = None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(", line)
        if head:
            name = head.group(1)
            out[name] = []
        elif name is not None and " = " in line:
            out[name].append(line)
    return out


@pytest.mark.parametrize("batch,length", [(1, 128), (2, 128), (4, 32), (32, 128)])
def test_embedding_tile_step_fuses_the_max_into_the_product(
    one_chip, no_compile_cache, batch, length
):
    """ISSUE 36's rule: no operation of the tile loop reads or writes a
    ``(B·L, tile)`` or ``(B, L, tile)`` float32 product outside a fusion;
    the loop's step is ONE fusion around the convolution whose root is the
    maximum over ``L``, writing ``(B, tile)``."""
    table = jax.ShapeDtypeStruct((RANK, V), jnp.float32, sharding=one_chip)
    seeds = jax.ShapeDtypeStruct((batch, length), jnp.int32, sharding=one_chip)
    kernel = jax.jit(partial(embed_ops._embed_topk_impl, k_best=K_BEST))
    comps = _computations(kernel.lower(table, seeds).compile().as_text())
    fused = {
        m.group(1)
        for lines in comps.values()
        for line in lines
        for m in [re.search(r"kind=\w+, calls=%([\w.\-]+)", line)]
        if m
    }
    _, tile = embed_ops._tile_plan(max(length, embed_ops._MIN_ROWS), V)
    product = re.compile(rf"= f32\[({batch * length}|{batch},{length}),{tile}\]")
    steps = []
    for name, lines in comps.items():
        if name in fused:
            continue
        for line in lines:
            assert not product.search(line), f"a product outside a fusion: {line[:160]}"
            if "while/body" not in line:
                continue
            assert not re.search(r" (reduce|convolution|dot)\(", line), (
                f"a stand-alone op in the tile loop: {line[:160]}"
            )
            if " fusion(" in line and "kind=kOutput" in line:
                steps.append(re.search(r"calls=%([\w.\-]+)", line).group(1))
    assert len(steps) == 1, steps
    body = comps[steps[0]]
    assert any(" convolution(" in line for line in body)
    root = next(line for line in body if "ROOT" in line)
    assert " reduce(" in root and f"{tile}]" in root, root[:160]


@pytest.mark.parametrize("batch,length", [(1, 1), (32, 128)])
def test_sharded_rule_lookup_fits_four_chips_under_the_traced_name(
    topo, no_compile_cache, batch, length
):
    """The vocabulary-sharded lookup at the four-chip cell's catalog: the
    chip's compiler accepts it, each device is handed a quarter of the
    19.23 GB of rule rows that no chip holds whole, arguments and
    temporaries fit a chip, the partials cross the mesh, and the module
    carries the name the benchmark's trace readers look for."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    mesh = Mesh(np.asarray(topo.devices), ("shard",))
    rows = NamedSharding(mesh, P("shard", None))
    ids = jax.ShapeDtypeStruct((V_SHARDED, K_MAX), jnp.int32, sharding=rows)
    confs = jax.ShapeDtypeStruct((V_SHARDED, K_MAX), jnp.float32, sharding=rows)
    seeds = jax.ShapeDtypeStruct(
        (batch, length), jnp.int32, sharding=NamedSharding(mesh, P(None, None))
    )
    compiled = sharded_recommend_fn(mesh, K_BEST).lower(ids, confs, seeds).compile()
    hlo = compiled.as_text()
    assert hlo.startswith("HloModule jit__recommend_batch_sharded")
    assert "all-gather" in hlo
    memory = compiled.memory_analysis()
    resident = V_SHARDED // 4 * K_MAX * 8
    assert V_SHARDED * K_MAX * 8 > CHIP_LIMIT  # no chip holds the rows whole
    assert resident <= memory.argument_size_in_bytes < resident + (1 << 20)
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < CHIP_LIMIT // 2


@pytest.mark.parametrize("batch,length", [(1, 1), (32, 128)])
def test_cross_shard_merge_ranks_candidates_not_the_vocabulary(
    topo, one_chip, no_compile_cache, batch, length
):
    """The merge of the four shards' partials works on their S·k_best = 40
    lanes alone: no instruction names a dimension of the vocabulary's
    width, and its temporaries are under 1 MB. Neither one shard's
    partial nor the whole sharded program names one either: each shard
    ranks its own candidate lanes (a dense epilogue names V in 15
    instructions of the partial at (1, 1), and a dense merge doubles
    them in the whole program), and the partial's temporaries stay
    small at every batch."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    n_shards = 4
    gathered = (n_shards, batch, K_BEST)
    merge = merge_partial_topk.lower(
        jax.ShapeDtypeStruct(gathered, jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct(gathered, jnp.float32, sharding=one_chip),
        k_best=K_BEST,
    ).compile()
    assert max(_widest_dims(merge.as_text())) <= max(batch, n_shards * K_BEST)
    assert merge.memory_analysis().temp_size_in_bytes < 1 << 20

    mesh = Mesh(np.asarray(topo.devices), ("shard",))
    rows = NamedSharding(mesh, P("shard", None))
    whole = sharded_recommend_fn(mesh, K_BEST).lower(
        jax.ShapeDtypeStruct((V_SHARDED, K_MAX), jnp.int32, sharding=rows),
        jax.ShapeDtypeStruct((V_SHARDED, K_MAX), jnp.float32, sharding=rows),
        jax.ShapeDtypeStruct(
            (batch, length), jnp.int32, sharding=NamedSharding(mesh, P(None, None))
        ),
    ).compile()
    v_loc = V_SHARDED // n_shards
    part = shard_partial_topk.lower(
        jax.ShapeDtypeStruct((v_loc, K_MAX), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((v_loc, K_MAX), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((batch, length), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        v=V_SHARDED, k_best=K_BEST,
    ).compile()

    def v_wide(compiled):
        return sum(d >= V_SHARDED for d in _widest_dims(compiled.as_text()))

    assert v_wide(whole) == v_wide(part) == 0
    assert part.memory_analysis().temp_size_in_bytes < EPILOGUE_TEMP_LIMIT


@pytest.mark.parametrize("batch,length", [(1, 1), (32, 128)])
def test_replicated_rule_lookup_writes_nothing_of_vocabulary_width(
    one_chip, no_compile_cache, batch, length
):
    """The replicated kernel at the Million Playlist Dataset's catalog:
    only the rule tables' own parameters are V rows long. No instruction
    writes a result with a dimension of the vocabulary's width (a dense
    epilogue writes a (B, V+1) score array and ranks it), and the
    temporaries are a few MB at a full batch of the longest bucket
    (579 MB for a dense epilogue)."""
    compiled = recommend_batch.lower(
        jax.ShapeDtypeStruct((V, K_MAX), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((V, K_MAX), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((batch, length), jnp.int32, sharding=one_chip),
        k_best=K_BEST,
    ).compile()
    wide = [op for op, dims in _results(compiled.as_text()) if dims and max(dims) >= V]
    assert wide and set(wide) == {"parameter"}, wide
    assert compiled.memory_analysis().temp_size_in_bytes < EPILOGUE_TEMP_LIMIT
