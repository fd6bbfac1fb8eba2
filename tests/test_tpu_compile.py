"""What the chip's compiler accepts, asked without a chip.

The TPU's compiler is installed here and compiles for a chip that is
described (`v5e:2x2`) and not attached, so these cases guard every later
PR at no chip time: the Pallas fold at the real 100k and 1M geometries
and every depth a bucket boost can take them to (held against what
`fold_engine` answers there), the program that seeds a 1M world, the
default tick, the 1M tick with the kernel in it, one sharded tick over
the described 2x2 mesh, the clone-scene fleet's `rooms.step` at its
benchmarked size, and the served siege's interest table build and scan
at 2^20 rows with and without its second level.  A compile that passes is not a chip run: nothing
executes, so no result or time is checked here.

This is the only file that describes a topology.  The description
happens inside the module-scoped `topo` fixture, never at import (only
one process may hold the TPU library; see section 2 of the
on-chip-measurement guide), and the compile cache is off around it: such
a compile can be written to the cache but not read back without a chip.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from noahgameframe_tpu.game import build_benchmark_world
from noahgameframe_tpu.game.combat import CombatModule
from noahgameframe_tpu.ops import stencil_pallas as sp
from noahgameframe_tpu.ops.stencil import CellTable


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here: skip
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _geometry(n):
    """(capacity, width, cell, victim bucket, attacker bucket) of
    build_benchmark_world(n)'s combat grid, from the module that sizes
    it (staggered arming: duty = dt / attack_period = 1/30)."""
    extent = max(64.0, float(np.sqrt(n / 0.4)))
    cap = 1 << int(np.ceil(np.log2(n)))
    m = CombatModule(extent=extent, radius=4.0)
    m._attacker_duty = 1.0 / 30.0
    return (cap, m.width, m.cell_size, m.resolved_bucket(cap),
            m.resolved_att_bucket(cap))


def _scatters(text):
    """(result shape, update shape) of every `scatter` in a compiled
    program."""
    import re

    shape_of = dict(re.findall(
        r"^\s*(?:ROOT )?(%[\w.\-]+) = (\w+\[[\d,]*\])", text, re.M))
    return [
        (m.group(1), shape_of[m.group(2)]) for m in re.finditer(
            r"= (\w+\[[\d,]*\])\S* scatter\(%[\w.\-]+, %[\w.\-]+, "
            r"(%[\w.\-]+)\)", text)]


def _attacker_side(text, table, rank_rows):
    """What PR 27 holds the compiled tick to: (the `gather`s under
    `nf.aoe.rank` whose result is `rank_rows`, the update operand of
    every `scatter` into the `table`-shaped attacker payload)."""
    import re

    rank_gathers = [
        line for line in text.splitlines()
        if re.search(r"= \w+\[%s\]\S* gather\(" % rank_rows, line)
        and "nf.aoe.rank" in line]
    updates = [update for result, update in _scatters(text)
               if result == f"f32[{table}]"]
    return rank_gathers, updates


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, sharding)


@pytest.mark.parametrize("boost", [1, 2, 4, 8])
@pytest.mark.parametrize("n,want,kernel_up_to", [
    (100_000, (125, 20, 6), 4),
    (1_000_000, (395, 16, 6), 2),
])
def test_combat_fold_pallas_compiles_natively(one_chip, n, want,
                                              kernel_up_to, boost):
    """The two benchmarked grids at the depths they are built with and
    at every doubling `auto_resize` may make on a live tick (the windows
    run at boost 2: 395 at 32/12, 125 at 40/12), up to
    `max_bucket_boost`.  Each case compiles what `fold_engine` answers
    there: the kernel (up to boost `kernel_up_to`), natively, or the XLA
    fold.  At the first doubling past the kernel the kernel is tried
    too: if Mosaic refuses it, it is for VMEM, and the rule had answered
    0, so the retrace bakes a fold that compiles."""
    from noahgameframe_tpu.game.combat import combat_fold_xla

    cap, width, cell, kv, ka = _geometry(n)
    assert (width, kv, ka) == want, "benchmark geometry moved"
    assert boost <= CombatModule().max_bucket_boost
    kv, ka = kv * boost, ka * boost
    rule = sp.fold_engine("tpu", width, kv, ka)
    assert rule == (1 if boost <= kernel_up_to else 0)
    cells = width * width

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def compiled_text(fold):
        return jax.jit(lambda vp, vs, ap, as_: fold(
            CellTable(vp, vs, jnp.int32(0), width, cell, kv),
            CellTable(ap, as_, jnp.int32(0), width, cell, ka))).lower(
            arg((cells * kv + 1, sp.N_VFEATS + 1), jnp.float32),
            arg((cap,), jnp.int32),
            arg((cells * ka + 1, sp.N_AFEATS + 1), jnp.float32),
            arg((cap,), jnp.int32),
        ).compile().as_text()

    if rule == 1 or boost == 2 * kernel_up_to:
        try:
            text = compiled_text(lambda v, a: sp.combat_fold_pallas(
                v, a, 4.0, interpret=False))
        except Exception as e:  # noqa: BLE001 -- the compiler's refusal
            assert "vmem" in str(e), e
            assert rule == 0, f"the rule chose a kernel Mosaic refuses: {e}"
        else:
            assert "tpu_custom_call" in text
    if rule == 0:
        text = compiled_text(lambda v, a: combat_fold_xla(v, a, 4.0))
        assert "tpu_custom_call" not in text


def test_world_seeding_fits_one_chip_at_1m(one_chip):
    """The program that seeds rows (store.create_many) at BASELINE
    config 4's capacity.  Found on the chip in PR 21: cleared by a
    scatter, the [2^20, 9, 29] stat page was relaid row-major, padded
    7.8x to two 8 GB temporaries, and the 1M world could not be built."""
    from noahgameframe_tpu.core import store

    cap = 1 << 20
    cs = build_benchmark_world(64, seed=0).kernel.state.classes["NPC"]
    assert cs.records["CommPropertyValue"].i32.shape[1:] == (9, 29)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    big = jax.tree.map(lambda x: arg((cap,) + x.shape[1:], x.dtype), cs)
    compiled = store._reset_and_write_rows.lower(
        big, arg((cap,), jnp.int32),
        arg((cap, cs.i32.shape[1]), jnp.int32),
        arg((cap, cs.f32.shape[1]), jnp.float32),
        arg((cap,) + cs.vec.shape[1:], jnp.float32),
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 1024 ** 3


def test_default_tick_compiles_for_one_chip(one_chip):
    """kernel._trace_step of the benchmark world (engine 0, the shipped
    default) at a 32,768-row capacity."""
    w = build_benchmark_world(20_000, seed=0)
    k, combat = w.kernel, w.combat
    k._ensure_aux()
    assert k.store.capacity("NPC") == 32_768
    state = _shapes(k.state, jax.tree.map(lambda _: one_chip, k.state))
    compiled = jax.jit(k._trace_step, donate_argnums=0).lower(state).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 0
    assert mem.temp_size_in_bytes < 16 * 1024 ** 3
    # the attacker side is priced by attackers: nothing under the rank
    # scope gathers by row, and the attacker table is scattered a
    # duty-sized chunk at a time (once outright, once in the loop's body)
    rows = combat.resolved_att_rows(32_768)
    assert rows == 2192  # 2 * ceil(32768 / 30), whole sublanes
    table = combat.width ** 2 * combat.resolved_att_bucket(32_768) + 1
    rank_gathers, updates = _attacker_side(
        compiled.as_text(), f"{table},8", "32768")
    assert rank_gathers == []
    assert updates == [f"f32[{rows},8]"] * 2


def test_sharded_tick_compiles_for_the_2x2_mesh(topo):
    """One sharded tick with live row migration (ShardedKernel +
    RowMigrationModule, the SpatialWorld preset) over the four described
    devices: migration and halos lower to collective-permutes."""
    from noahgameframe_tpu.parallel.mesh import SHARD_AXIS
    from noahgameframe_tpu.parallel.shard import world_shardings
    from noahgameframe_tpu.parallel.spatial import SpatialGeom, SpatialWorld

    mesh = Mesh(np.asarray(topo.devices).reshape(4), (SHARD_AXIS,))
    geom = SpatialGeom(
        extent=256.0, cell_size=4.0, width=64, n_shards=4, bucket=16,
        att_bucket=8, radius=4.0, mig_budget=64, speed=1.0,
        attack_period=30)
    sw = SpatialWorld(geom, mesh=mesh)
    sw.bank_size = 2048
    sw._build_kernel(4 * sw.bank_size)  # state stays on the CPU
    k = sw.kernel
    k._ensure_aux()
    shardings = world_shardings(k.state, mesh)

    def step(st):
        st2, _out = k._trace_step(st)
        return st2

    compiled = jax.jit(
        step, in_shardings=(shardings,), out_shardings=shardings,
        donate_argnums=0,
    ).lower(_shapes(k.state, shardings)).compile()
    assert "collective-permute" in compiled.as_text()
    # each device holds a quarter of the banks, not all of them
    bank = 4 * sw.bank_size * (5 + 3) * 4  # i32[cap,5] + f32[cap,1,3]
    assert compiled.memory_analysis().argument_size_in_bytes < bank


def test_fleet_tick_compiles_for_one_chip_at_5k_rooms(one_chip,
                                                      monkeypatch):
    """`rooms.step` of `clone-rooms-5k` (benchmarks/configs): the tick
    of a 96-NPC, 16-unit room vmapped over 8,192 slots.  The bank and
    the program's temporaries fit a chip with room for the benchmark's
    six bank copies, and the named scopes survive `vmap` in the compiled
    text, where the per-layer readers look for them."""
    from noahgameframe_tpu.game import BenchmarkRoomRecipe

    # the fold's choice sees the chip the program is compiled for
    monkeypatch.setattr(sp, "trace_platform", lambda: "tpu")
    room = BenchmarkRoomRecipe(96, 16.0, player_capacity=4)(0)
    k = room.kernel
    k._ensure_aux()
    assert k.store.capacity("NPC") == 128
    fleet = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((8192,) + np.shape(x),
                                       jnp.asarray(x).dtype,
                                       sharding=one_chip), k.state)

    def rooms_step(st):
        st2, out = jax.vmap(k._trace_step)(st)
        return st2, out["summary"]

    compiled = jax.jit(rooms_step, donate_argnums=0).lower(fleet).compile()
    mem = compiled.memory_analysis()
    # 2.10 GB of banks + 1.20 GB of temporaries (0.59 GB scattered)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        < 6 * 1024 ** 3
    text = compiled.as_text()
    for scope in ("nf.schedule", "nf.phase.CombatModule.aoe", "nf.diff",
                  "nf.aoe.rank", "nf.aoe.table", "nf.aoe.fold",
                  "nf.aoe.pull", "nf.summary"):
        assert f"vmap({scope})" in text or f")/{scope}" in text, scope
    # 16 sorted attackers a room are gathered and scattered, not its 128
    # rows; the chunk's slices did not turn into loops over the rooms
    rank_gathers, updates = _attacker_side(text, "794624,8", "8192,128")
    assert rank_gathers == []
    assert updates == ["f32[8192,16,8]"] * 2
    assert "while/body/dynamic_slice" not in text
    # the victim table is gathered by index vectors, batched over the
    # rooms, and brought no loop over them: the one loop of the build is
    # the attacker chunks'
    _sends_no_victim_row(text, "8192,128", 8192 * 321)
    loops = [line for line in text.splitlines()
             if " while(" in line and "nf.aoe" in line]
    assert len(loops) == 1 and "nf.aoe.table/while\"" in loops[0], loops
    # a room's grid is 4 cells wide, 4 lanes in 128: the XLA fold
    assert room.combat.engine_baked == 0
    assert "tpu_custom_call" not in text


def _sends_no_victim_row(text, bank, slots):
    """What PR 31 holds a compiled tick to: nothing is scattered into a
    table of the victims' `slots`, and the one scatter whose updates are
    the bank's rows (`bank`: the shape's leading dimensions) is the
    un-sort of `slot_of`, single words."""
    scatters = _scatters(text)
    into_table = [s for s in scatters if s[0] in (
        f"f32[{slots},6]", f"f32[6,{slots}]")]
    assert into_table == [], "rows are sent to the victim table again"
    by_bank = [s for s in scatters if s[1].startswith(f"f32[{bank}")]
    assert by_bank == [], by_bank
    loops = [line for line in text.splitlines()
             if " while(" in line and "nf.aoe.rank" in line]
    assert loops == [], "the build's index passes loop"


_TICKS_1M = {}


def _compile_1m_tick(one_chip, boost, depths):
    """`kernel.step` of `npc-1m` traced for the chip, once a boost for
    the tests that read it.  The world is built small and its NPC bank
    described at 2^20 rows: every shape of the tick follows from the
    bank's and from the module's extent."""
    if boost in _TICKS_1M:
        return _TICKS_1M[boost]
    from noahgameframe_tpu.game import GameWorld, WorldConfig

    was, sp.trace_platform = sp.trace_platform, lambda: "tpu"
    try:
        extent = float(np.sqrt(1_000_000 / 0.4))
        w = GameWorld(WorldConfig(npc_capacity=128, extent=extent, seed=0,
                                  middleware=False))
        w.start()
        w.scene.create_scene(1, width=extent)
        k, combat = w.kernel, w.combat
        k._ensure_aux()
        combat._attacker_duty = 1.0 / 30.0  # arm_all's staggered arming
        combat._bucket_boost = boost
        cap = 1 << 20
        assert (combat.width, combat.resolved_bucket(cap),
                combat.resolved_att_bucket(cap)) == (395,) + depths
        state = _shapes(k.state, jax.tree.map(lambda _: one_chip, k.state))
        state = state.replace(classes={**state.classes, "NPC": jax.tree.map(
            lambda x: jax.ShapeDtypeStruct((cap,) + x.shape[1:], x.dtype,
                                           sharding=one_chip),
            state.classes["NPC"])})
        compiled = jax.jit(k._trace_step,
                           donate_argnums=0).lower(state).compile()
    finally:
        sp.trace_platform = was
    _TICKS_1M[boost] = (compiled, combat.engine_baked)
    return _TICKS_1M[boost]


@pytest.mark.parametrize("boost,depths", [(1, (16, 6)), (2, (32, 12))])
def test_1m_tick_bakes_one_kernel(one_chip, boost, depths):
    """`kernel.step` of `npc-1m` at the depths it is built with and at
    those its window runs at (boost 2: 32/12), traced for the chip: the
    fold is the Pallas kernel and it is the program's only custom call,
    and the victim table is gathered from the sorted list: no scatter
    sends the bank's 2^20 rows to it (until PR 31 one did, 89 ms of a
    147 ms tick)."""
    compiled, engine_baked = _compile_1m_tick(one_chip, boost, depths)
    assert engine_baked == 1
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    _sends_no_victim_row(text, "1048576", 395 * 395 * depths[0] + 1)
    # 1.09 GB at 32/12, the run table among them (0.70 GB scattered)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 1024 ** 3


def test_1m_tick_hands_over_planes_not_masks(one_chip):
    """The 1M tick's diff leaves the program as bit planes by column,
    128 KB a column, and no `pred[2^20, C]` mask is among its outputs
    (until PR 34 two were, 51 MB a frame for the serving host to fetch).
    The per-column counts ride the summary: no output of their own."""
    compiled, _ = _compile_1m_tick(one_chip, 2, (32, 12))
    _, out = compiled.out_info
    diff = {(c, b): (i.shape, str(i.dtype))
            for c, banks in out["diff"].items() for b, i in banks.items()}
    assert {dt for _, dt in diff.values()} == {"uint32"}
    assert diff["NPC", "i32"][0] == (47, (1 << 20) // 32)
    assert diff["NPC", "vec"][0] == (2, (1 << 20) // 32)
    wide = [i.shape for i in jax.tree.leaves(compiled.out_info)
            if str(i.dtype) == "bool" and i.shape == (1 << 20, 47)]
    assert wide == []
    assert "diff_cols" not in out
    # the compare's mask is a temporary, folded where it is made: the
    # program text has no widened copy of it
    assert "u32[1048576,47]" not in compiled.as_text()


def test_siege_tick_with_its_second_level_compiles(one_chip, monkeypatch):
    """`kernel.step` of `siege-zipf-1m` as its window runs it (32/12 and
    a second level of 8,192 hot cells, 512 victims and 32 attackers
    deep, walkers with homes), traced for the chip: the base fold stays
    the kernel, the second level's loops are in the program, and it fits
    the chip beside the world."""
    from noahgameframe_tpu.game import GameWorld, WorldConfig

    monkeypatch.setattr(sp, "trace_platform", lambda: "tpu")
    extent = float(np.sqrt(1_000_000 / 0.4))
    w = GameWorld(WorldConfig(npc_capacity=128, extent=extent, seed=0,
                              middleware=False))
    w.start()
    w.scene.create_scene(1, width=extent)
    k, combat = w.kernel, w.combat
    w.movement.set_homes(np.zeros((4096, 2), np.float32),
                         np.zeros(128, np.int32), 64.0)
    k._ensure_aux()
    combat._attacker_duty = 1.0 / 30.0
    combat._bucket_boost = 2
    combat._spill = (8192, 512, 32)
    cap = 1 << 20
    assert combat.resolved_spill(cap) == (8192, 512, 32)

    def described(x):
        return jax.ShapeDtypeStruct((cap,) + x.shape[1:], x.dtype,
                                    sharding=one_chip)

    state = _shapes(k.state, jax.tree.map(lambda _: one_chip, k.state))
    state = state.replace(
        classes={**state.classes,
                 "NPC": jax.tree.map(described, state.classes["NPC"])},
        aux={name: jax.tree.map(described, leaf)
             for name, leaf in state.aux.items()})
    compiled = jax.jit(k._trace_step, donate_argnums=0).lower(state).compile()
    text = compiled.as_text()
    assert combat.engine_baked == 1
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "nf.aoe.spill/while" in text
    # both levels of the victim table are gathered, 9,187,105 slots
    _sends_no_victim_row(text, "1048576", 395 * 395 * 32 + 1 + 8192 * 512)
    # temporaries: 1.69 GB with the run tables of both levels (1.76 GB
    # when the table was scattered)
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5 * 1024 ** 3


@pytest.mark.parametrize("sizes", [(44, 0, 0), (88, 4096, 2048)])
def test_siege_interest_step_compiles_for_one_chip(one_chip, sizes):
    """The served siege's visibility answer at its real size: 2^20 rows
    binned into 198 x 198 interest cells, 32 observers, at the depth the
    capacity alone sizes and at the sizes the role's breach policy gives
    the crowd (a doubling, then 4,096 hot cells 2,048 deep).  The table
    is built with no row sent: the only scatter is of the cells' heads
    (`slot_of` is read by nobody here and dropped).  The level's scope
    is in the program exactly when the level is."""
    from noahgameframe_tpu.ops.interest import quantize, visible_candidates

    n, sessions, extent, radius = 1 << 20, 32, 1581.1388, 8.0
    width = int(np.ceil(extent / radius))
    bucket, cells, depth = sizes

    def step(pos, alive, scene, group, obs, obs_scene, obs_group):
        q, inside = quantize(pos, alive, extent)
        res = visible_candidates(
            pos, inside, scene, group, obs, obs_scene, obs_group,
            radius=radius, cell_size=radius, width=width, bucket=bucket,
            spill=(cells, depth))
        return q, res.rows, res.ok, res.stats

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(step).lower(
        arg((n, 3)), arg((n,), jnp.bool_), arg((n,)), arg((n,)),
        arg((sessions, 2)), arg((sessions,)), arg((sessions,))).compile()
    text = compiled.as_text()
    assert [s for s in _scatters(text) if "1048576" in s[1]] == []
    assert f"s32[{sessions},{9 * (bucket + depth)}]" in text
    assert ("nf.interest.spill" in text) == bool(cells)
    assert "nf.interest.bin" in text and "nf.interest.scan" in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes < 4 << 30
