"""Tick engine: lifecycle, jitted tick, heartbeats, events, diffs.

The final test is Milestone A / BASELINE config 1: Tutorial3 parity —
objects with property callbacks, heartbeats and events, driven through the
plugin-manager lifecycle (reference Tutorial/Tutorial3/HelloWorld3Module).
"""

import contextlib
import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from noahgameframe_tpu.core import StoreConfig
from noahgameframe_tpu.core.datatypes import Bank
from noahgameframe_tpu.kernel import (
    Kernel,
    Module,
    ObjectEvent,
    Plugin,
    PluginManager,
)
from noahgameframe_tpu.kernel.kernel import (
    DIFF_WORD,
    pack_diff_planes,
    unpack_diff_plane,
)

from fixtures import base_registry

EVENT_ON_DEAD = 11


class RegenModule(Module):
    """HP regen on a heartbeat + death event emission — the canonical
    batchable gameplay module."""

    name = "RegenModule"

    def init(self):
        self.kernel.schedule.register_timer("NPC", "RegenBeat")
        self.add_phase("regen", self.phase_regen, order=50)

    def phase_regen(self, state, ctx):
        store = ctx.store
        cs = state.classes["NPC"]
        fired = ctx.fired("NPC", "RegenBeat")
        spec = store.spec("NPC")
        hp_c, mx_c, rg_c = (
            spec.slots["HP"].col,
            spec.slots["MAXHP"].col,
            spec.slots["HPREGEN"].col,
        )
        hp = cs.i32[:, hp_c]
        new_hp = jnp.minimum(hp + cs.i32[:, rg_c], cs.i32[:, mx_c])
        hp = jnp.where(fired & cs.alive, new_hp, hp)
        cs = cs.replace(i32=cs.i32.at[:, hp_c].set(hp))
        # emit deaths (hp dropped to 0 elsewhere): here just demo emit API
        ctx.emit(EVENT_ON_DEAD, "NPC", (hp <= 0) & cs.alive)
        return state.replace(classes={**state.classes, "NPC": cs})


def build_pm(dt=1.0, cap=64):
    pm = PluginManager()
    kernel = Kernel(
        base_registry(),
        StoreConfig(default_capacity=cap, capacities={"NPC": cap, "Player": cap}),
        dt=dt,
        class_names=["IObject", "Player", "NPC"],
    )
    plugin = Plugin("TestPlugin", [kernel, RegenModule()])
    pm.register_plugin(plugin)
    return pm, kernel


def test_lifecycle_and_build():
    pm, kernel = build_pm()
    pm.start()
    assert kernel.store is not None and kernel.state is not None
    # timer slot allocated on NPC
    assert kernel.store.config.timer_slots.get("NPC") == 1
    assert pm.find_module(RegenModule).name == "RegenModule"


def test_heartbeat_fires_on_schedule_and_counts_down():
    pm, kernel = build_pm(dt=1.0)
    pm.start()
    g = kernel.create_object("NPC", {"HP": 10, "MAXHP": 100, "HPREGEN": 5})
    # every 2 ticks, 3 times total
    kernel.state = kernel.schedule.set_timer(
        kernel.state, kernel.store, g, "RegenBeat", interval_s=2.0, count=3
    )
    hps = []
    for _ in range(10):
        pm.run_once()
        hps.append(kernel.get_property(g, "HP"))
    # fires at tick>=2, every 2 ticks, 3 times: 10->15->20->25 then stops
    assert hps[-1] == 25
    assert sorted(set(hps)) == [10, 15, 20, 25]


def test_heartbeat_forever_and_max_clamp():
    pm, kernel = build_pm(dt=1.0)
    pm.start()
    g = kernel.create_object("NPC", {"HP": 95, "MAXHP": 100, "HPREGEN": 10})
    kernel.state = kernel.schedule.set_timer(
        kernel.state, kernel.store, g, "RegenBeat", interval_s=1.0, count=-1
    )
    pm.run(5)
    assert kernel.get_property(g, "HP") == 100  # clamped at MAXHP


def test_property_diff_events_fire_with_rows():
    pm, kernel = build_pm(dt=1.0)
    pm.start()
    seen = []
    kernel.register_property_event(
        "NPC", "HP", lambda c, p, rows: seen.append((c, p, rows.tolist()))
    )
    g = kernel.create_object("NPC", {"HP": 50, "MAXHP": 100, "HPREGEN": 1})
    _, row = kernel.store.row_of(g)[0], kernel.store.row_of(g)[1]
    kernel.state = kernel.schedule.set_timer(
        kernel.state, kernel.store, g, "RegenBeat", interval_s=1.0
    )
    pm.run(2)  # first firing lands one interval after arming
    assert seen and seen[0] == ("NPC", "HP", [row])


def test_host_set_property_fires_callback_sync():
    pm, kernel = build_pm()
    pm.start()
    seen = []
    kernel.register_property_event("NPC", "HP", lambda c, p, rows: seen.append(rows.tolist()))
    g = kernel.create_object("NPC", {"HP": 50})
    kernel.set_property(g, "HP", 60)
    assert len(seen) == 1
    kernel.set_property(g, "HP", 60)  # no-op write -> no callback
    assert len(seen) == 1


def test_device_event_emission_to_batch_and_object_subscribers():
    pm, kernel = build_pm(dt=1.0)
    pm.start()
    batch_seen = []
    obj_seen = []
    kernel.events.subscribe_batch(
        EVENT_ON_DEAD, lambda cname, mask, params: batch_seen.append(int(mask.sum()))
    )
    g_dead = kernel.create_object("NPC", {"HP": 0, "MAXHP": 10, "HPREGEN": 0})
    kernel.create_object("NPC", {"HP": 5, "MAXHP": 10, "HPREGEN": 0})
    kernel.events.subscribe_object(
        g_dead, EVENT_ON_DEAD, lambda guid, eid, args: obj_seen.append((guid, eid))
    )
    pm.run_once()
    assert batch_seen == [1]
    assert obj_seen == [(g_dead, EVENT_ON_DEAD)]


def test_create_chain_order_and_destroy_events():
    pm, kernel = build_pm()
    pm.start()
    events = []
    kernel.register_class_event(lambda g, c, ev: events.append((c, ev)), "NPC")
    g = kernel.create_object("NPC")
    chain = [ev for c, ev in events]
    assert chain == [
        ObjectEvent.CREATE_NODATA,
        ObjectEvent.CREATE_LOADDATA,
        ObjectEvent.CREATE_BEFORE_EFFECT,
        ObjectEvent.CREATE_EFFECTDATA,
        ObjectEvent.CREATE_AFTER_EFFECT,
        ObjectEvent.CREATE_HASDATA,
        ObjectEvent.CREATE_FINISH,
    ]
    events.clear()
    kernel.destroy_object(g)
    assert [ev for c, ev in events] == [ObjectEvent.BEFORE_DESTROY, ObjectEvent.DESTROY]


def test_deferred_destroy_flushes_next_frame():
    pm, kernel = build_pm()
    pm.start()
    g = kernel.create_object("NPC")
    kernel.destroy_object(g, deferred=True)
    assert kernel.store.live_count("NPC") == 1
    pm.run_once()
    assert kernel.store.live_count("NPC") == 0


def test_device_death_reconciles_and_fires_destroy():
    """A phase clears `alive` on device; host sees DESTROY next tick."""
    pm, kernel = build_pm(dt=1.0)

    class ReaperModule(Module):
        name = "Reaper"

        def init(self):
            self.add_phase("reap", self.phase, order=60)

        def phase(self, state, ctx):
            cs = state.classes["NPC"]
            spec = ctx.store.spec("NPC")
            hp = cs.i32[:, spec.slots["HP"].col]
            cs = cs.replace(alive=cs.alive & (hp > 0))
            return state.replace(classes={**state.classes, "NPC": cs})

    pm.plugins["TestPlugin"].add(ReaperModule())
    pm._register_module(pm.plugins["TestPlugin"].modules[-1])
    pm.start()
    destroyed = []
    kernel.register_class_event(
        lambda g, c, ev: destroyed.append(g) if ev == ObjectEvent.DESTROY else None, "NPC"
    )
    g1 = kernel.create_object("NPC", {"HP": 0})
    g2 = kernel.create_object("NPC", {"HP": 10})
    pm.run_once()
    assert destroyed == [g1]
    assert kernel.store.live_count("NPC") == 1


def test_determinism_same_seed_same_world():
    def run():
        pm, kernel = build_pm(dt=1.0)
        pm.start()
        for i in range(8):
            kernel.create_object("NPC", {"HP": 10 + i, "MAXHP": 100, "HPREGEN": 2})
        kernel.state = kernel.schedule.set_timer_rows(
            kernel.state, "NPC", np.arange(8), "RegenBeat", 1.0
        )
        pm.run(5)
        return np.asarray(kernel.state.classes["NPC"].i32)

    a, b = run(), run()
    np.testing.assert_array_equal(a, b)


def test_tutorial3_parity_1k_objects():
    """BASELINE config 1: 1k objects with heartbeat + property callbacks +
    events, full lifecycle, multi-tick run (reference Tutorial3)."""
    pm, kernel = build_pm(dt=1.0, cap=1100)
    pm.start()
    changed_rows = set()
    kernel.register_property_event(
        "NPC", "HP", lambda c, p, rows: changed_rows.update(rows.tolist())
    )
    n = 1000
    kernel.state, guids, rows = kernel.store.create_many(
        kernel.state,
        "NPC",
        n,
        values={"HP": [50] * n, "MAXHP": [100] * n, "HPREGEN": [3] * n},
    )
    kernel.state = kernel.schedule.set_timer_rows(
        kernel.state, "NPC", rows, "RegenBeat", interval_s=2.0, count=-1
    )
    pm.run(5)  # tick indices 0..4 -> fires at ticks 2 and 4
    hp = np.asarray(kernel.store.column(kernel.state, "NPC", "HP"))
    assert (hp[rows] == 56).all()
    assert len(changed_rows) == n
    assert kernel.tick_count == 5


def test_dead_entity_still_delivers_its_device_events():
    """Regression: events emitted by an entity that dies the same tick must
    reach per-object subscribers (events dispatch before death reconcile)."""
    pm, kernel = build_pm(dt=1.0)

    class EmitAndReap(Module):
        name = "EmitAndReap"

        def init(self):
            self.add_phase("go", self.phase, order=60)

        def phase(self, state, ctx):
            cs = state.classes["NPC"]
            spec = ctx.store.spec("NPC")
            hp = cs.i32[:, spec.slots["HP"].col]
            dying = (hp <= 0) & cs.alive
            ctx.emit(77, "NPC", dying)
            cs = cs.replace(alive=cs.alive & ~dying)
            return state.replace(classes={**state.classes, "NPC": cs})

    pm.plugins["TestPlugin"].add(EmitAndReap())
    pm._register_module(pm.plugins["TestPlugin"].modules[-1])
    pm.start()
    g = kernel.create_object("NPC", {"HP": 0})
    heard = []
    kernel.events.subscribe_object(g, 77, lambda gd, e, a: heard.append(gd))
    pm.run_once()
    assert heard == [g]
    assert kernel.store.live_count("NPC") == 0


def test_create_object_bad_property_leaks_nothing():
    """Regression: a typo'd property name must not corrupt host bookkeeping."""
    pm, kernel = build_pm()
    pm.start()
    live_before = kernel.store.live_count("NPC")
    guids_before = len(kernel.store.guid_map)
    with pytest.raises(KeyError):
        kernel.create_object("NPC", {"Typo": 1})
    assert kernel.store.live_count("NPC") == live_before
    assert len(kernel.store.guid_map) == guids_before


def test_set_phases_replaces_not_accumulates():
    """Regression: recomposing phases must not duplicate or retain stale
    phases (the hot-reload path)."""
    pm, kernel = build_pm(dt=1.0)
    pm.start()
    g = kernel.create_object("NPC", {"HP": 10, "MAXHP": 100, "HPREGEN": 5})
    kernel.state = kernel.schedule.set_timer(
        kernel.state, kernel.store, g, "RegenBeat", 1.0
    )
    # recompose exactly as reload_plugin does
    kernel.set_phases([p for m in pm.modules.values() for p in m.phases])
    kernel.compile()
    pm.run(2)  # one firing
    assert kernel.get_property(g, "HP") == 15  # +5 once, not twice


# ------------------------------------------------ property fan-out (PR 25)

class ChurnModule(Module):
    """Writes i32, f32 and vec columns of two classes on a schedule that
    leaves some columns, and every fourth tick the whole world, unchanged."""

    name = "ChurnModule"

    def init(self):
        self.add_phase("churn", self.phase_churn, order=60)

    def phase_churn(self, state, ctx):
        t = state.tick
        busy = t % 4 != 3  # a tick of every four changes nothing
        classes = dict(state.classes)

        def bump(cname, pname, where, by):
            cs = classes[cname]
            slot = ctx.store.spec(cname).slot(pname)
            bank = getattr(cs, slot.bank.value)
            rows = jnp.arange(bank.shape[0])
            hit = where(rows) & busy
            hit = hit.reshape(hit.shape + (1,) * (bank.ndim - 2))
            col = bank[:, slot.col] + jnp.where(hit, by, 0).astype(bank.dtype)
            classes[cname] = cs.replace(
                **{slot.bank.value: bank.at[:, slot.col].set(col)})

        bump("NPC", "HP", lambda r: (r + t) % 3 == 0, 1)
        bump("NPC", "ATK_VALUE", lambda r: (r % 2 == 0) & (t % 2 == 0), 1)
        bump("NPC", "MoveSpeed", lambda r: r >= 0, 0.25)  # no diff flag
        bump("NPC", "Position", lambda r: (r % 4 == 1) & (t % 2 == 1), 1.0)
        bump("Player", "MoveSpeed", lambda r: (r + t) % 2 == 0, 0.5)
        bump("Player", "Level", lambda r: (r >= 0) & (t % 3 == 0), 1)
        return state.replace(classes=classes)


# registration order, classes interleaved; NPC.HP has two subscribers
FANOUT_SUBS = [
    ("NPC", "HP", "a"), ("Player", "MoveSpeed", "a"), ("NPC", "Position", "a"),
    ("NPC", "HP", "b"), ("Player", "Level", "a"), ("NPC", "ATK_VALUE", "a"),
    ("NPC", "MAXHP", "a"), ("Player", "Position", "a"),
    ("NPC", "MoveSpeed", "a"), ("NPC", "TargetPos", "a"),
]


def build_churn(subscribe=True, cap=64):
    pm = PluginManager()
    kernel = Kernel(
        base_registry(),
        StoreConfig(default_capacity=cap, capacities={"NPC": cap, "Player": cap}),
        dt=1.0,
        class_names=["IObject", "Player", "NPC"],
    )
    pm.register_plugin(Plugin("ChurnPlugin", [kernel, ChurnModule()]))
    pm.start()
    calls = []
    if subscribe:
        kernel.force_diff_property("NPC", "ATK_VALUE")
        for cname, pname, tag in FANOUT_SUBS:
            kernel.register_property_event(
                cname, pname,
                lambda c, p, rows, tag=tag: calls.append((tag, c, p, rows)))
    for i in range(11):
        kernel.create_object("NPC", {"HP": 10 + i, "MAXHP": 100})
    for i in range(5):
        kernel.create_object("Player", {"Level": 1 + i})
    return kernel, calls


@functools.lru_cache(maxsize=None)
def reference_diffs(ticks):
    """Plain numpy over the banks of a twin world ticked a frame at a
    time, nobody subscribed: per tick, class -> bank -> `bool[capacity,
    cols]`, the extracted cells of alive rows that changed."""
    twin, _ = build_churn(subscribe=False)
    twin.force_diff_property("NPC", "ATK_VALUE")
    frames = []
    for _ in range(ticks):
        before = jax.device_get(twin.state.classes)
        twin.tick()
        after = jax.device_get(twin.state.classes)
        frame = {}
        for cname in twin.store.class_order:
            for bank in Bank:
                fm = twin.diff_columns(cname, bank)
                if not fm.any():
                    continue
                ne = (getattr(before[cname], bank.value)
                      != getattr(after[cname], bank.value))
                if bank == Bank.VEC:
                    ne = ne.any(axis=-1)
                frame.setdefault(cname, {})[bank.value] = (
                    ne & after[cname].alive[:, None] & fm[None, :])
        frames.append(frame)
    return frames


def calls_from_reference(kernel, frames):
    """The fan-out worked out from `reference_diffs`: per tick, per
    subscribed (class, property) in order of first registration, the
    changed rows, once per subscriber."""
    order = {}
    for cname, pname, tag in FANOUT_SUBS:
        order.setdefault((cname, pname), []).append(tag)
    want = []
    for frame in frames:
        for (cname, pname), tags in order.items():
            slot = kernel.store.spec(cname).slot(pname)
            m = frame.get(cname, {}).get(slot.bank.value)
            if m is None:
                continue
            rows = np.flatnonzero(m[:, slot.col])
            if rows.size:
                want += [(tag, cname, pname, rows.tolist()) for tag in tags]
    return want


def _drive(kernel, path, ticks):
    if path == "tick":
        return [kernel.tick() for _ in range(ticks)]
    if path == "train":
        kernel.configure_train(3)  # whole trains and a ragged tail
        return kernel.train(ticks)
    from noahgameframe_tpu.parallel import ShardedKernel

    sk = ShardedKernel(kernel, n_devices=8)
    sk.place()
    return [sk.tick() for _ in range(ticks)]


@pytest.mark.parametrize("path", ["tick", "train", "sharded"])
def test_property_fanout_equals_reference_diff(path):
    kernel, calls = build_churn()
    outs = _drive(kernel, path, 8)
    assert len(outs) == 8
    got = [(tag, c, p, rows.tolist()) for tag, c, p, rows in calls]
    assert got == calls_from_reference(kernel, reference_diffs(8))
    for _, _, _, rows in calls:
        assert rows.dtype == np.intp and rows.ndim == 1 and rows.size
        assert (np.diff(rows) > 0).all()
    seen = {(c, p) for _, c, p, _ in got}
    # every bank of both classes, the forced column and both subscribers
    assert {("NPC", "HP"), ("NPC", "ATK_VALUE"), ("NPC", "Position"),
            ("Player", "MoveSpeed"), ("Player", "Level")} == seen
    assert [g[0] for g in got if g[1:3] == ("NPC", "HP")][:2] == ["a", "b"]
    # an unchanged column, and a changed one with no diff flag, stay silent
    assert not seen & {("NPC", "MAXHP"), ("Player", "Position"),
                       ("NPC", "MoveSpeed"), ("NPC", "TargetPos")}


@pytest.mark.parametrize("path", ["tick", "train", "sharded"])
def test_diff_planes_and_column_counts_equal_reference_diff(path):
    """What a tick hands over in place of the masks: every plane unpacks
    to its column of the reference diff, the summary's per-column counts
    are that column's cells, and their sum is the class's `diff_count`."""
    kernel, _ = build_churn()
    outs = _drive(kernel, path, 8)
    frames = reference_diffs(8)
    assert any(m.any() for f in frames for d in f.values() for m in d.values())
    for out, frame in zip(outs, frames):
        assert {c: set(d) for c, d in out.diff.items()} == {
            c: set(d) for c, d in frame.items()}
        cells = 0
        for cname, banks in frame.items():
            cap = kernel.store.capacity(cname)
            for bank_name, m in banks.items():
                planes = np.asarray(out.diff[cname][bank_name])
                assert planes.dtype == np.uint32
                assert planes.shape == (m.shape[1], -(-cap // DIFF_WORD))
                for col in range(m.shape[1]):
                    assert unpack_diff_plane(planes[col]).tolist() == \
                        np.flatnonzero(m[:, col]).tolist()
                assert out.diff_cols[cname][bank_name].tolist() == \
                    m.sum(axis=0).tolist()
            per_class = sum(int(m.sum()) for m in banks.values())
            assert int(out.diff_count[cname]) == per_class == sum(
                int(c.sum()) for c in out.diff_cols[cname].values())
            cells += per_class
        assert out.counters["diff_cells"] == cells


@pytest.mark.parametrize("fill", ["sparse", "half", "empty", "full"])
@pytest.mark.parametrize("n", [8, 33, 128, 1 << 17])
def test_diff_planes_round_trip(n, fill):
    """`unpack_diff_plane` inverts `pack_diff_planes` at capacities that
    are and are not a multiple of 32; the rows come out ascending."""
    rng = np.random.default_rng(n)
    p = {"sparse": 0.01, "half": 0.5, "empty": 0.0, "full": 1.0}[fill]
    m = rng.random((n, 5)) < p
    m[:, 4] = False  # an empty column beside the others
    planes = np.asarray(jax.jit(pack_diff_planes)(jnp.asarray(m)))
    assert planes.dtype == np.uint32
    assert planes.shape == (5, -(-n // DIFF_WORD))
    for col in range(5):
        rows = unpack_diff_plane(planes[col])
        assert rows.dtype == np.intp
        assert rows.tolist() == np.flatnonzero(m[:, col]).tolist()
    assert [int(c) for c in np.unpackbits(
        planes.view(np.uint8), axis=1).sum(axis=1)] == m.sum(axis=0).tolist()


class _HostOnly:
    """Stands where a bank's diff planes stand: it can be read whole,
    and counts the reads; indexing it (a device program per call)
    fails."""

    def __init__(self, planes, reads, key=None):
        self._planes, self._reads, self._key = planes, reads, key
        self.shape = planes.shape

    def __array__(self, *a, **kw):
        self._reads.append((self._key, self._planes.shape))
        return np.asarray(self._planes)

    def __getitem__(self, idx):
        raise AssertionError("the fan-out indexed a device array")


class _SpanProbe:
    """A tracer that notes which log records fall inside one span."""

    def __init__(self, name, records):
        self.name, self.records = name, records
        self.entered, self.inside = 0, []

    @contextlib.contextmanager
    def span(self, name, **args):
        n0 = len(self.records)
        yield
        if name == self.name:
            self.entered += 1
            self.inside += self.records[n0:]


@pytest.mark.parametrize("path", ["tick", "train", "sharded"])
def test_fanout_mask_counters_and_round_trips(path):
    """More than three subscribed properties a class cost at most one
    read per (class, bank), and only of a bank a subscribed column of
    which changed; a tick that changed nothing costs none."""
    kernel, calls = build_churn()
    pairs = {(c, kernel.store.spec(c).slot(p).bank.value)
             for c, p, _ in FANOUT_SUBS}
    assert len({p for c, p, _ in FANOUT_SUBS if c == "NPC"}) > 3
    reads, per_tick, extracted = [], [], set()
    post = kernel._post_tick

    def guarded(out, summary, **kw):
        extracted.update((c, b) for c, d in out.diff.items() for b in d)
        out.diff = {c: {b: _HostOnly(m, reads, (c, b)) for b, m in d.items()}
                    for c, d in out.diff.items()}
        was = (kernel.fanout_mask_fetches, kernel.fanout_mask_bytes,
               kernel.fanout_mask_columns, len(reads), len(calls))
        n0 = len(calls)
        post(out, summary, **kw)
        now = (kernel.fanout_mask_fetches, kernel.fanout_mask_bytes,
               kernel.fanout_mask_columns, len(reads), len(calls))
        per_tick.append(tuple(b - a for a, b in zip(was, now))
                        + (len({(c, p) for _, c, p, _ in calls[n0:]}),))

    kernel._post_tick = guarded
    _drive(kernel, path, 8)
    assert len(per_tick) == 8
    # NPC has no flagged f32 column, so no f32 planes: five pairs of six
    pairs &= extracted
    assert len(pairs) == 5
    for fetches, nbytes, columns, n_reads, n_calls, props in per_tick:
        assert fetches == n_reads <= len(pairs)
        assert (fetches > 0) == (nbytes > 0) == (n_calls > 0)
        assert fetches <= columns == props  # a column unpacked a property
    # state.tick 3 and 7 (the fourth and eighth ticks) change nothing
    assert [t[:3] for t in per_tick][3::4] == [(0, 0, 0)] * 2
    # Player's vec bank has a subscriber (Position) and never changes:
    # its planes are never read
    assert {key for key, _ in reads} == pairs - {("Player", "vec")}
    assert kernel.fanout_mask_fetches == len(reads)
    assert kernel.fanout_mask_bytes == 4 * sum(
        int(np.prod(shape)) for _, shape in reads)


def test_unsubscribed_change_reads_no_plane():
    """A frame that changes cells, none in a subscribed column: the
    summary's column counts say so and no plane is fetched."""
    kernel, _ = build_churn(subscribe=False)
    calls = []
    for pname in ("MAXHP", "TargetPos"):  # flagged columns nobody writes
        kernel.register_property_event(
            "NPC", pname, lambda c, p, rows: calls.append((c, p)))
    outs = [kernel.tick() for _ in range(4)]
    assert any(int(o.diff_count["NPC"]) for o in outs)
    assert calls == []
    assert (kernel.fanout_mask_fetches, kernel.fanout_mask_bytes,
            kernel.fanout_mask_columns) == (0, 0, 0)


def test_fanout_counters_stay_zero_without_subscribers():
    """The tick-1m situation: diffs every tick, nobody subscribed."""
    kernel, _ = build_churn(subscribe=False)
    outs = [kernel.tick() for _ in range(4)]
    assert any(int(v) for o in outs for v in o.diff_count.values())
    assert (kernel.fanout_mask_fetches, kernel.fanout_mask_bytes,
            kernel.fanout_mask_columns) == (0, 0, 0)


def test_fanout_props_compiles_nothing(caplog):
    """Mask shapes no other test has, so any device program the block ran
    would have to be compiled inside its span."""
    kernel, calls = build_churn(cap=88)
    probe = _SpanProbe("fanout.props", caplog.records)
    kernel.tracer = probe
    with caplog.at_level(logging.WARNING), jax.log_compiles():
        for _ in range(6):
            kernel.tick()
    assert any("Compiling" in r.getMessage() for r in caplog.records)
    assert probe.entered == 6 and calls
    assert [r.getMessage() for r in probe.inside] == []
