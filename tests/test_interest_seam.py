"""The interest answer read from the sorted list, held to the table read.

`ops/interest.interest_sorted` sorts a class by interest cell and lays
out no table; `scan_sorted` reads each observer's nine cells as windows
of the sorted list.  What a two-level cell table held is the oracle:
`ops/stencil.build_cell_table` over the same rows, read here slot by
slot through `grid_view` / `spill_view`, as the scan read it before it
read the list.  Every case runs through the function and through both
serve engines' programs (`interest.build` + `interest.scan`, and
`serve.prepare` + `serve.scan`), and holds:

- `ok` equal to the table read's everywhere, `rows` equal where `ok`
  holds and in every base slot (0 in an empty one, as the table's);
- the build's counts (`STAT_NAMES`) equal to the table build's;
- nothing the build hands over is as long as the table was.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from noahgameframe_tpu.game.world import GameWorld, WorldConfig
from noahgameframe_tpu.net.roles.base import RoleConfig
from noahgameframe_tpu.net.roles.game import GameRole
from noahgameframe_tpu.ops.interest import (
    STAT_NAMES,
    _interest_feats,
    interest_sorted,
    quantize,
    scope_mask,
    visible_candidates,
)
from noahgameframe_tpu.ops.serving import SENTINEL, init_seen
from noahgameframe_tpu.ops.stencil import STENCIL, build_cell_table

RADIUS = 8.0
EXTENT = 64.0
WIDTH = 8  # the role's grid: ceil(EXTENT / RADIUS)
N = 256  # the role's NPC capacity
BUCKET = 12  # auto_bucket(N, WIDTH): what the role states
S_PAD = 8


def make_case(name: str):
    """(pos, alive, scene, group, obs, obs_scene, obs_group, obs_valid,
    spill) of one named world, numpy, from a fixed seed."""
    rng = np.random.default_rng(sum(map(ord, name)))
    pos = rng.uniform(0.5, EXTENT - 0.5, (N, 2)).astype(np.float32)
    alive = np.ones(N, bool)
    scene = np.ones(N, np.float32)
    group = np.zeros(N, np.float32)
    obs = rng.uniform(1.0, EXTENT - 1.0, (S_PAD, 2)).astype(np.float32)
    obs_scene = np.ones(S_PAD, np.float32)
    obs_group = np.zeros(S_PAD, np.float32)
    valid = np.ones(S_PAD, bool)
    spill = (0, 0)
    if name == "crowd":
        # four cells 40 deep where the base holds 12 and the level keeps
        # 16 more for the first two: the cap and the drops both engage
        for i, (cx, cy) in enumerate([(1, 1), (2, 1), (5, 4), (6, 6)]):
            pos[i * 40:(i + 1) * 40] = rng.uniform(
                (8 * cx + 0.2, 8 * cy + 0.2), (8 * cx + 7.8, 8 * cy + 7.8),
                (40, 2))
        obs[:4] = [[15.5, 12.0], [20.0, 10.0], [44.0, 36.0], [52.0, 52.0]]
        alive[rng.random(N) < 0.05] = False
        spill = (2, 16)
    elif name == "empty":
        # twenty rows in one quadrant, every other cell empty
        alive[20:] = False
        pos[:20] = rng.uniform(2.0, 22.0, (20, 2))
    elif name == "edges":
        obs[:] = [[0.0, 0.0], [63.9, 0.1], [0.1, 63.9], [64.0, 64.0],
                  [32.0, 0.0], [0.0, 32.0], [63.99, 31.0], [31.0, 64.0]]
        pos[:64] = rng.choice([0.0, 0.3, 63.7, 64.0], (64, 2))
    elif name == "out_of_extent":
        pos[:48] = rng.uniform(-6.0, 70.0, (48, 2))
        obs[:4] = [[1.0, 1.0], [63.0, 63.0], [1.0, 63.0], [63.0, 1.0]]
    elif name == "scoped":
        scene = rng.choice([1.0, 2.0], N).astype(np.float32)
        group = rng.choice([0.0, 0.0, 1.0, 2.0], N).astype(np.float32)
        obs_scene = rng.choice([1.0, 2.0], S_PAD).astype(np.float32)
        obs_group = rng.choice([0.0, 1.0, 2.0], S_PAD).astype(np.float32)
    elif name == "padded":
        valid[5:] = False
        obs[5:] = obs[0]  # a padded slot stands where a session does
    else:
        assert name == "uniform"
    return pos, alive, scene, group, obs, obs_scene, obs_group, valid, spill


CASES = ["uniform", "crowd", "empty", "edges", "out_of_extent", "scoped",
         "padded"]


@jax.jit(static_argnames="spill")
def table_read(pos, alive, scene, group, obs, obs_scene, obs_group, spill):
    """The oracle: the two-level table over the in-extent rows, and each
    observer's nine cells read from its base level and, for the cells
    that own a row, from its second level: rows, ok and the table
    build's counts."""
    pos3 = jnp.pad(pos, ((0, 0), (0, 1)))
    _q, inside = quantize(pos3, alive, EXTENT)
    table = build_cell_table(
        pos, inside, _interest_feats(pos, scene, group), RADIUS, WIDTH,
        BUCKET, spill_cells=spill[0], spill_bucket=spill[1])
    ox = jnp.floor(obs[:, 0] * (1.0 / RADIUS)).astype(jnp.int32)
    oy = jnp.floor(obs[:, 1] * (1.0 / RADIUS)).astype(jnp.int32)

    def read(slots, present):
        occ = (slots[..., -1] > 0) & present[:, None]
        dxv = slots[..., 1] - obs[:, None, 0]
        dyv = slots[..., 2] - obs[:, None, 1]
        within = (dxv * dxv + dyv * dyv) <= RADIUS * RADIUS
        scoped = scope_mask(slots[..., 3], slots[..., 4],
                            obs_scene[:, None], obs_group[:, None])
        return slots[..., 0].astype(jnp.int32), occ & within & scoped

    nine = []
    for dy, dx in STENCIL:
        yy, xx = oy + dy, ox + dx
        in_grid = (yy >= 0) & (yy < WIDTH) & (xx >= 0) & (xx < WIDTH)
        nine.append((jnp.clip(yy, 0, WIDTH - 1), jnp.clip(xx, 0, WIDTH - 1),
                     in_grid))
    grid = table.grid_view()
    got = [read(grid[yy, xx], g) for yy, xx, g in nine]
    if spill[0]:
        level = table.spill_view()
        hot_of = table.hot_of.reshape(WIDTH, WIDTH)
        for yy, xx, g in nine:
            hot = hot_of[yy, xx]
            got.append(read(level[jnp.maximum(hot, 0)], g & (hot >= 0)))
    st = table.stats
    stats = jnp.stack([table.dropped, st.hot_cells, st.rows_max,
                       st.spill_rows]).astype(jnp.int32)
    return (jnp.concatenate([r for r, _ in got], axis=1),
            jnp.concatenate([o for _, o in got], axis=1), stats)


visible = jax.jit(visible_candidates, static_argnames=(
    "radius", "cell_size", "width", "bucket", "spill"))


@pytest.fixture(scope="module")
def oracle():
    """name -> the table read of that case (valid observers only)."""
    done = {}

    def get(name):
        if name not in done:
            case = make_case(name)
            rows, ok, stats = table_read(
                *(jnp.asarray(a) for a in case[:7]), spill=case[8])
            done[name] = (np.asarray(rows),
                          np.asarray(ok) & case[7][:, None],
                          np.asarray(stats))
        return done[name]

    return get


@pytest.fixture(scope="module")
def role():
    world = GameWorld(WorldConfig(
        npc_capacity=N, player_capacity=64, extent=EXTENT,
        combat=False, movement=False, regen=False, middleware=False,
    ))
    world.start()
    r = GameRole(
        RoleConfig(6, 0, "SeamGame", "127.0.0.1", 0),
        backend="py", world=world, cross_server_sync=False,
        interest_radius=RADIUS,
    )
    r.server.send_raw = lambda c, m, b: True
    assert r._interest_grid() == (RADIUS, WIDTH)
    assert r.resolved_interest("NPC") == (BUCKET, 0, 0)
    return r


def banks(role, case):
    """The case written into the role's NPC and Player banks, as the
    programs take them: (evec, ei32, alive, pvec, pi32, obs_rows)."""
    pos, alive, scene, group, obs, obs_scene, obs_group, _valid, spill = case
    k = role.kernel
    if role.resolved_interest("NPC")[1:] != spill:
        role._interest_spill["NPC"] = spill  # the sizes the role states
        role._interest_jit.clear()
        role._serve_jit.clear()
        role._serve_geom = {}
    assert role.resolved_interest("NPC") == (BUCKET,) + tuple(spill)

    def put(cname, xy, sc, gr):
        spec = k.store.spec(cname)
        cs = k.state.classes[cname]
        p, s_, g = (spec.slots[n].col for n in ("Position", "SceneID",
                                                "GroupID"))
        rows = jnp.arange(len(xy))
        vec = cs.vec.at[rows, p, :2].set(jnp.asarray(xy))
        i32 = (cs.i32.at[rows, s_].set(jnp.asarray(sc, jnp.int32))
               .at[rows, g].set(jnp.asarray(gr, jnp.int32)))
        return vec, i32

    evec, ei32 = put("NPC", pos, scene, group)
    pvec, pi32 = put("Player", obs, obs_scene, obs_group)
    return (evec, ei32, jnp.asarray(alive), pvec, pi32,
            jnp.arange(S_PAD, dtype=jnp.int32))


def no_table(seam):
    """Nothing the build hands over is as long as the base table."""
    for leaf in jax.tree.leaves(seam):
        assert leaf.shape[0] < WIDTH * WIDTH * BUCKET, leaf.shape


@pytest.mark.parametrize("engine", ["ops", "interest_scan", "serve.scan"])
@pytest.mark.parametrize("name", CASES)
def test_the_scan_reads_what_the_table_held(role, oracle, name, engine):
    case = make_case(name)
    want_rows, want_ok, want_stats = oracle(name)
    k9 = 9 * BUCKET
    assert want_ok.shape == (S_PAD, 9 * (BUCKET + case[-1][1]))
    if name == "crowd":
        stats = dict(zip(STAT_NAMES, want_stats.tolist()))
        assert stats["hot_cells"] > case[-1][0] and stats["dropped"] > 0
        assert want_ok[:, k9:].any()  # the level is read
    if name == "out_of_extent":
        assert not np.isin(want_rows[want_ok], np.arange(48)[
            np.any((case[0][:48] < 0) | (case[0][:48] > EXTENT), axis=1)
        ]).any()
    valid = case[7]
    if engine == "ops":
        pos, alive, scene, group, obs, obs_scene, obs_group = (
            jnp.asarray(a) for a in case[:7])
        _q, inside = quantize(jnp.pad(pos, ((0, 0), (0, 1))), alive, EXTENT)
        res = visible(pos, inside, scene, group, obs, obs_scene, obs_group,
                      radius=RADIUS, cell_size=RADIUS, width=WIDTH,
                      bucket=BUCKET, spill=case[-1])
        rows, ok, stats = map(np.asarray, (res.rows, res.ok, res.stats))
        ok = ok & valid[:, None]
    elif engine == "interest_scan":
        evec, ei32, alive, pvec, pi32, obs_rows = banks(role, case)
        q, seam = role._interest_build("NPC")(evec, ei32, alive)
        no_table(seam)
        rows, ok, widest = role._interest_scan("NPC", S_PAD)(
            seam, pvec, pi32, obs_rows, jnp.asarray(valid))
        rows, ok, stats = map(np.asarray, (rows, ok, seam.stats))
        assert int(widest) == ok.sum(axis=1).max()
    else:
        evec, ei32, alive, pvec, pi32, obs_rows = banks(role, case)
        qver = jnp.zeros((N,), jnp.int32)
        prev_q = jnp.full((N, 3), -1, jnp.int32)
        _q, qver, _prev, seam = role._serve_prepare("NPC")(
            evec, ei32, alive, qver, prev_q)
        no_table(seam)
        m = want_ok.shape[1]
        seen = init_seen(S_PAD, m)
        delta, widest = role._serve_scan("NPC", S_PAD)(
            seam, pvec, pi32, obs_rows, jnp.asarray(valid),
            jnp.ones((N,), bool), jnp.zeros((N,), jnp.int32), qver,
            seen.rows, seen.gen, seen.qver)
        want_vis = np.sort(np.where(want_ok, want_rows, SENTINEL), axis=1)
        np.testing.assert_array_equal(np.asarray(delta.vis), want_vis)
        assert int(widest) == want_ok.sum(axis=1).max()
        np.testing.assert_array_equal(np.asarray(seam.stats), want_stats)
        return
    np.testing.assert_array_equal(ok, want_ok)
    np.testing.assert_array_equal(rows[ok], want_rows[want_ok])
    np.testing.assert_array_equal(rows[:, :k9], want_rows[:, :k9])
    np.testing.assert_array_equal(stats, want_stats)


def test_the_siege_build_lays_out_no_table():
    """The served siege's sizes (2^20 rows, 198 x 198 cells, 88 deep,
    4,096 x 2,048 in the level): the table was 11.8M slots; nothing the
    build hands over now reaches the base level's 3.45M."""
    n, width, bucket, spill = 1 << 20, 198, 88, (4096, 2048)

    def build(pos, alive, scene, group):
        return interest_sorted(pos, alive, scene, group, RADIUS, width,
                               bucket, spill)

    f32 = jnp.float32
    seam = jax.eval_shape(
        build, jax.ShapeDtypeStruct((n, 3), f32),
        jax.ShapeDtypeStruct((n,), jnp.bool_), jax.ShapeDtypeStruct((n,), f32),
        jax.ShapeDtypeStruct((n,), f32))
    assert seam.feats.shape == (n + bucket + spill[1], 5)
    for leaf in jax.tree.leaves(seam):
        assert leaf.shape[0] < width * width * bucket, leaf.shape
