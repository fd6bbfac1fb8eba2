"""Game role: the kernel-backed world server behind the proxy.

Reference: NFGameServerNet_ServerPlugin + NFGameServerNet_ClientPlugin —
accepts proxy connections and serves ~30 message handlers (enter/leave
game, role CRUD, swap scene, move, chat;
`NFCGameServerNet_ServerModule.cpp:31-73`), registers at World with 10 s
reports (`NFCGameServerToWorldModule.cpp:34-130`), and binds the
scene/AOI callbacks so property & record changes serialize into `NFMsg`
sync messages sent via the proxy with explicit client lists
(`OnPropertyEnter` `:271-400` and the §3.3 data-flow spine).

TPU inversion: instead of per-write callbacks, the role pulls each tick's
flag-masked diff off the device as bit planes by column (already counted
per column by the jit'd step) and fans the changed cells out as grouped
property-sync messages to every player in the broadcast set — one device
fetch per changed bank per tick instead of one callback per write.
"""

from __future__ import annotations

import dataclasses
import json as _json
import os
import time as _time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ...core.datatypes import Bank, DataType, Guid
from ...telemetry.pipeline import (
    StageClock,
    TraceContext,
    TraceError,
    decode_trace,
    encode_trace,
    stage_timing_enabled,
    trace_sample_n,
)
from ...telemetry.tracing import span
from ...core.store import RecordOp
from ...game.world import GameWorld, WorldConfig
from ...kernel.kernel import (
    ObjectEvent,
    REC_ADDED,
    REC_REMOVED,
    REC_UPDATED,
    TickOutputs,
)
from ...utils.hostio import gather_rows
from ...persist.codec import (
    record_row_struct,
    serialize_properties,
    serialize_records,
    snapshot_object,
)
from ..defines import TRACE_MSG_IDS, EventCode, MsgID, ServerState, ServerType
from ..transport import EV_DISCONNECTED
from ..wire import (
    AckEventResult,
    ServerInfoExt,
    AckPlayerEntryList,
    AckPlayerLeaveList,
    AckRoleLiteInfoList,
    Ident,
    Message,
    MsgBase,
    ObjectPropertyFloat,
    ObjectPropertyInt,
    ObjectPropertyList,
    ObjectPropertyObject,
    ObjectPropertyString,
    ObjectPropertyVector2,
    ObjectPropertyVector3,
    ObjectRecordAddRow,
    ObjectRecordBase,
    ObjectRecordFloat,
    ObjectRecordInt,
    ObjectRecordList,
    ObjectRecordObject,
    ObjectRecordRemove,
    ObjectRecordString,
    ObjectRecordVector3,
    PlayerEntryInfo,
    PropertyFloat,
    PropertyInt,
    PropertyObject,
    PropertyString,
    PropertyVector2,
    PropertyVector3,
    RecordAddRowStruct,
    RecordFloat,
    RecordInt,
    RecordObject,
    RecordString,
    RecordVector3,
    ReqAcceptTask,
    ReqAckCreateGuild,
    ReqAckCreateTeam,
    ReqAckJoinGuild,
    ReqAckJoinTeam,
    ReqAckLeaveGuild,
    ReqAckLeaveTeam,
    ReqAckOprTeamMember,
    ReqAckPlayerChat,
    ReqAckPlayerMove,
    ReqAckSwapScene,
    ReqAckUseItem,
    ReqAckUseSkill,
    ReqCompeleteTask,
    ReqCreateRole,
    ReqDeleteRole,
    ReqEnterGameServer,
    ReqRoleList,
    ReqSearchGuild,
    ReqSetFightHero,
    ReqSwitchServer,
    ReqWearEquip,
    AckSearchGuild,
    AckSwitchServer,
    RoleLiteInfo,
    SearchGuildObject,
    SwitchServerData,
    TakeOffEquip,
    TeamInfo,
    TeammemberInfo,
    Vector2,
    Vector3,
    ident_key as _ident_key,
    unwrap,
    wrap,
)
from .base import RoleConfig, ServerRole

_IdentKey = Tuple[int, int]

# row-identified wire targets (hero/equip record rows) ride Ident.index
# with THIS svrid tag — row 0 is valid, and protoc clients always send
# the required field (zeroed when untargeted), so a plain falsy test on
# the index cannot discriminate "no target" from "row 0"
ROW_TARGET_SVRID = 1


def guid_ident(g: Guid) -> Ident:
    """GUID ↔ wire Ident (`NFMsgBase.proto` Ident{svrid,index})."""
    return Ident(svrid=g.head, index=g.data)


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


@dataclasses.dataclass
class Session:
    ident: Ident
    conn_id: int  # proxy connection that owns this client
    account: str = ""
    guid: Optional[Guid] = None


class GameRole(ServerRole):
    server_type = int(ServerType.GAME)

    def __init__(
        self,
        config: RoleConfig,
        backend: str = "auto",
        world: Optional[GameWorld] = None,
        scene_id: int = 1,
        sync_classes: Sequence[str] = ("Player", "NPC"),
        skill_damage: int = 10,
        data_agent=None,
        role_store=None,
        autosave_seconds: float = 30.0,
        cross_server_sync: bool = True,
        batch_sync_min: int = 256,
        interest_radius: Optional[float] = None,
        serve_batch: Optional[bool] = None,
        serve_overlap: Optional[bool] = None,
        tick_train: Optional[int] = None,
        checkpoint_dir=None,
        checkpoint_seconds: float = 30.0,
        resume: bool = False,
        journal_dir=None,
        journal_segment_bytes: int = 1 << 20,
        persist_store=None,
        persist_wal_dir=None,
        persist_drain_timeout: float = 3.0,
    ) -> None:
        # (class, prop) diffs with >= batch_sync_min changed rows go out
        # as ONE columnar ACK_BATCH_PROPERTY message per (cell, conn)
        # instead of per-entity messages — the served-path fast lane
        self.batch_sync_min = batch_sync_min
        # with a radius, Position leaves on the per-session interest
        # stream instead (u16-quantized, delta-gated, device-filtered):
        # each client gets only entities within `interest_radius` of its
        # avatar — group-granular broadcast is full-world fan-out when a
        # group is busy (round-3: 24.5 MB/frame at 100k / 500 sessions)
        self.interest_radius = interest_radius
        # Verlet skin for the interest grids (NF_VERLET_SKIN knob,
        # ops/verlet.py): > 0 inflates the interest cell size to
        # radius + skin and amortizes the per-flush argsort across
        # flushes via a displacement-gated cache carried in
        # WorldState.aux ("verlet/interest/<class>")
        from ...ops.verlet import skin_from_env

        self._interest_skin = (
            float(skin_from_env()) if interest_radius is not None else 0.0
        )
        self._interest_jit: Dict[Tuple[str, int], object] = {}
        # the interest table's sizes, per class, beyond what the
        # capacity alone gives (`resolved_interest`): a doubling of the
        # bucket and the second level's (cells, depth).  Nothing sets
        # them but `_observe_interest`, from what a frame's own table
        # build counted (a crowd breaching the budget below)
        self._interest_boost: Dict[str, int] = {}
        self._interest_spill: Dict[str, Tuple[int, int]] = {}
        self.interest_overflow_budget = 1e-4  # dropped/live a frame
        self.interest_max_boost = 8
        self.interest_resizes = 0
        self._interest_log_muted = False
        # the frame bank: what the newest frame's table build counted,
        # per class (STAT_NAMES of ops/interest.py + candidates_max)
        self.interest_last: Dict[str, Dict[str, int]] = {}
        # classes with a create/destroy since the last interest flush
        # (visible sets can change without any Position diff)
        self._interest_dirty: set = set()
        self._last_obs_sig: Optional[tuple] = None
        # --- batched serving edge (ISSUE 13) -------------------------
        # NF_SERVE_BATCH=1 swaps the per-session Python serve loops for
        # one vmap-over-sessions device kernel (ops/serving.py) plus
        # SoA host assembly (net/serving.py).  NF_SERVE_OVERLAP=1
        # (implies batch) additionally double-buffers the serve
        # snapshot: the interest Position lane is computed against the
        # PRE-tick state and its assembly/encode/send overlaps the
        # device tick — clients see those diffs exactly one tick later
        # (bounded staleness <= 1 tick, journaled in the run meta).
        def _env_flag(name: str, explicit: Optional[bool]) -> bool:
            if explicit is not None:
                return bool(explicit)
            return os.environ.get(name, "0") == "1"

        self.serve_overlap = (
            _env_flag("NF_SERVE_OVERLAP", serve_overlap)
            and interest_radius is not None
        )
        self.serve_batch = self.serve_overlap or (
            _env_flag("NF_SERVE_BATCH", serve_batch)
            and interest_radius is not None
        )
        # --- K-tick trains (ISSUE 20) --------------------------------
        # NF_TICK_TRAIN=K (K >= 2) runs the device tick as one K-frame
        # lax.scan megadispatch per due frame: every host-consumed lane
        # comes back stacked [K, ...] (kernel.TRAIN_LANE_SPEC), fetched
        # once, and fanned out in tick order — journal digest marks,
        # death attribution and counters stay per-tick exact at 1/K the
        # dispatch+fetch cost.  Election: trains need K >= 2 and lose
        # to overlap mode (overlap serves each frame against the
        # pre-tick snapshot; inside a train there is no between-frame
        # host window), so NF_SERVE_OVERLAP=1 keeps K at 1.  The
        # resulting staleness contract (clients see a burst of K frames
        # per train, i.e. diffs up to K-1 ticks old) is journaled like
        # the overlap contract so replay honors the same engine.
        k_train = (int(tick_train) if tick_train is not None
                   else _env_int("NF_TICK_TRAIN", 0))
        self.tick_train = k_train if (k_train >= 2
                                      and not self.serve_overlap) else 0
        from ..serving import SessionTable

        self._session_table = SessionTable()
        self._serve_jit: Dict[tuple, object] = {}
        # per-class device position-version state (role-held, NOT kernel
        # aux: kernel.invalidate() drops aux on recompile, but versions
        # must survive recompiles or every client would get a full
        # resend) — cname -> (qver [C] i32, prev_q [C,3] i32)
        self._serve_qver: Dict[str, tuple] = {}
        # host-side guid mirrors as of the LAST serve run: gone lists
        # name entities whose rows may already be freed (guid zeroed in
        # the live arrays), so the wire payload gathers from these
        self._serve_prev_guids: Dict[str, tuple] = {}
        # overlap mode: deferred Position-lane inputs from last frame
        self._serve_pending: Dict[str, object] = {}
        self.game_world = world if world is not None else GameWorld(
            WorldConfig(combat=False, movement=False, regen=True)
        ).start()
        self.kernel = self.game_world.kernel
        if self.tick_train:
            self.kernel.configure_train(self.tick_train)
        self.scene = self.game_world.scene
        self.scene_id = scene_id
        self.sync_classes = tuple(sync_classes)
        self.skill_damage = skill_damage
        if scene_id not in self.scene.scenes:
            self.scene.create_scene(scene_id)
        info = self.scene.scenes[scene_id]
        if 1 not in info.groups:
            self.scene.request_group(scene_id)
        # sessions by client ident; reverse map guid -> ident key
        self.sessions: Dict[_IdentKey, Session] = {}
        self._guid_session: Dict[Guid, _IdentKey] = {}
        # account -> role rows; backed by role_store when one is attached
        self.roles: Dict[str, List[RoleLiteInfo]] = {}
        self.role_store = role_store
        self.data_agent = data_agent
        self._last_tick = 0.0
        self.autosave_seconds = autosave_seconds
        self._last_autosave = 0.0
        # crash recovery: periodic atomic whole-world checkpoints
        # (persist/checkpoint.py) + resume-on-boot; re-registration with
        # world/master happens through the normal on-connect path
        from pathlib import Path as _Path

        self.checkpoint_dir = _Path(checkpoint_dir) if checkpoint_dir else None
        self.checkpoint_seconds = checkpoint_seconds
        self._last_checkpoint = 0.0
        # many-worlds room directory (parallel/rooms.py), attached via
        # attach_rooms(); None = this role serves its single GameWorld
        self.rooms = None
        # flight recorder (replay/journal.py): when a journal dir is
        # given, every dispatched net event + a per-tick on-device state
        # digest is logged so the run can be re-executed offline.  The
        # digest must be baked into the compiled tick, so flip it on
        # BEFORE anything can trigger the first compile.
        self.journal = None
        self._journal_dir = _Path(journal_dir) if journal_dir else None
        self._journal_segment_bytes = int(journal_segment_bytes)
        if self._journal_dir is not None:
            self.kernel.enable_digest()
        super().__init__(config, backend=backend)
        reg = self.telemetry.registry
        self._ckpt_counter = reg.counter(
            "nf_checkpoints_total", "atomic world checkpoints written"
        )
        self._reshard_resets = reg.counter(
            "nf_reshard_view_resets_total",
            "session views force-reset because a reshard moved their rows"
        )
        self._recover_counter = reg.counter(
            "nf_recoveries_total", "world restores from checkpoint (resume)"
        )
        if resume and self.checkpoint_dir is not None:
            if (self.checkpoint_dir / "meta.json").exists():
                # restores device banks + host identity; a torn pair
                # raises (load_world's array_tick guard) rather than
                # resuming a corrupt world
                self.game_world.load(self.checkpoint_dir)
                self._recover_counter.inc()
            # no checkpoint yet -> cold start
        # world-tick latency, separate from the pump's frame histogram
        # (a pump frame with no tick due is ~free; mixing them would
        # drown the tick percentiles in poll noise)
        self._tick_hist = self.telemetry.registry.histogram(
            "nf_game_tick_seconds", "world tick latency (kernel + modules)"
        )
        self.world_link = self.add_upstream(
            "world",
            [t for t in config.targets if t.server_type == int(ServerType.WORLD)],
            register_msg=MsgID.GTW_GAME_REGISTERED,
            refresh_msg=MsgID.STS_SERVER_REPORT,
        )
        # world relay: public Player state forwarded up; remote games' sync
        # delivered to local clients (cross-game visibility without the
        # reference's world-side object mirror — the batched messages relay
        # verbatim; NFCWorldNet_ServerModule.cpp:600-830)
        self.cross_server_sync = cross_server_sync
        if cross_server_sync:  # gate BOTH directions (isolated realms)
            from .world import CROSS_SYNC_MSGS

            for msg in CROSS_SYNC_MSGS:
                self.world_link.on(msg, self._on_world_sync)
        # PVP rooms minted by matchmaking, pending their ectype step
        self._pvp_rooms: Dict = {}
        # cross-game-server switch (NFCGSSwichServerModule): staged blobs
        # by player ident, world-link handlers for the re-home protocol
        self._switch_blobs: Dict = {}
        self.world_link.on(MsgID.SWITCH_SERVER_DATA, self._on_switch_data)
        self.world_link.on(MsgID.REQ_SWITCH_SERVER, self._on_switch_in)
        self.world_link.on(MsgID.ACK_SWITCH_SERVER, self._on_switch_ack)
        # a playable default stat table when the deployment didn't load one
        # (reference ships Property*.xlsx configs; LevelModule refreshes the
        # JOBLEVEL stat row from it on level-up)
        pc = self.game_world.property_config
        if not np.any(pc._base):
            pc.fill_linear(
                0,
                base={"MAXHP": 100, "MAXMP": 50, "MAXSP": 50, "HPREGEN": 1,
                      "ATK_VALUE": 10, "DEF_VALUE": 5, "MOVE_SPEED": 30000},
                per_level={"MAXHP": 20, "ATK_VALUE": 2, "DEF_VALUE": 1},
            )
            pc.freeze()
        if self.data_agent is not None:
            # bind BEFORE our own class-event hooks so load-on-create runs
            # inside the COE chain ahead of the enter-scene snapshot
            self.data_agent.bind(self.kernel)
        self.kernel.register_class_event(self._on_class_event, "Player")
        self.kernel.register_class_event(self._on_npc_event, "NPC")
        # subscribe every public OR private property of the synced classes;
        # the kernel fires these for host writes synchronously AND from the
        # device diff planes after each tick — one mechanism for the whole
        # spine.  Public changes broadcast to the (scene, group); private-
        # only changes go to the owner's client (GetBroadCastObject
        # semantics, NFCSceneAOIModule.cpp:531-593).
        self._changed: Dict[Tuple[str, str], np.ndarray] = {}
        for cname in self.sync_classes:
            spec = self.kernel.store.spec(cname)
            for slot in spec.slots.values():
                if slot.prop.public or slot.prop.private:
                    self.kernel.register_property_event(
                        cname, slot.prop.name, self._queue_change
                    )
        # record sync: host per-op hooks + device record diffs feed one
        # accumulator, flushed per frame (the round-1 gap: bag/equip/buff
        # changes mid-session never reached clients;
        # reference NFCGameServerNet_ServerModule.cpp:75-81)
        # (cname, rname) -> {"add": set, "del": set, "upd": dict, "swap": list}
        self._rec_changed: Dict[Tuple[str, str], Dict[str, object]] = {}
        self.kernel.subscribe_record_host(self._on_record_host)
        self._synced_records: Dict[Tuple[str, str], bool] = {}  # -> public?
        for cname in self.sync_classes:
            spec = self.kernel.store.spec(cname)
            for rname, rs in spec.records.items():
                d = rs.rec
                if d.public or d.private or d.upload:
                    self._synced_records[(cname, rname)] = bool(d.public)
                    self.kernel.register_record_diff(
                        cname, rname, self._on_record_diff
                    )
        if self.interest_radius is not None:
            # creates/destroys change visible sets without a Position
            # diff — mark the class dirty so the gated interest flush runs
            def _mark_dirty(_g: Guid, cn: str, _ev) -> None:
                self._interest_dirty.add(cn)

            for cname in self.sync_classes:
                if self._interest_ok(cname):
                    self.kernel.register_class_event(_mark_dirty, cname)
        if self._journal_dir is not None:
            from ...ops.verlet import skin_from_env
            from ...replay.journal import (
                JournalWriter,
                SRC_SERVER,
                SRC_WORLD,
            )

            cfg = self.game_world.config
            # guid allocation is wall-clock seeded (epoch micros); wire
            # messages CARRY guids back into mutating handlers (e.g. the
            # switch ack destroys by guid), so an unpinned clock is a
            # hidden replay input.  Pin the allocator to a pure counter
            # from here on and journal the seed — replay pins the
            # offline role to the same point and every post-pin guid
            # comes out bit-identical (ISSUE 10)
            guid_seed = self.kernel.store.guids.pin()
            self.journal = JournalWriter(
                self._journal_dir,
                segment_bytes=self._journal_segment_bytes,
                meta={
                    "server_id": config.server_id,
                    "name": config.name,
                    "world_seed": cfg.seed,
                    "dt": cfg.dt,
                    "start_tick": self.kernel.tick_count,
                    "resumed": bool(resume),
                    "verlet_skin": float(skin_from_env()),
                    "guid_seed": int(guid_seed),
                    # serving-edge staleness contract: with overlap on,
                    # the interest Position lane serves the PRE-tick
                    # snapshot (clients run <= 1 tick behind); replay
                    # must honor the same engine to stay digest-clean
                    "serve_batch": bool(self.serve_batch),
                    "serve_overlap": bool(self.serve_overlap),
                    # trains deliver diffs/events in a burst after each
                    # K-tick megadispatch: staleness <= K-1 ticks.  The
                    # per-tick marks are stamped from in-lane tick
                    # numbers, so replay (one real tick per mark) is
                    # bit-identical with the knob flipped either way.
                    "tick_train": int(self.tick_train),
                    "serve_staleness_ticks": (
                        self.tick_train - 1 if self.tick_train
                        else (1 if self.serve_overlap else 0)
                    ),
                },
            )
            # tap BOTH dispatch choke points: client/proxy traffic on the
            # listening server, world commands/switches on the world link
            # — together with the tick marks this is the complete
            # host→device input stream
            self.server.dispatch.tap = self._journal_tap(SRC_SERVER)
            self.world_link.dispatch.tap = self._journal_tap(SRC_WORLD)
            reg = self.telemetry.registry
            self._jrn_bytes = reg.counter(
                "nf_journal_bytes_total", "flight-recorder bytes appended"
            )
            self._jrn_segments = reg.counter(
                "nf_journal_segments_total", "flight-recorder segments opened"
            )
            self._jrn_ticks = reg.counter(
                "nf_journal_ticks_total", "ticks journaled with a digest"
            )
            self._jrn_sampled = [0, 0, 0]  # bytes, segments, ticks
            self._journal_pump_counters()
        # write-behind durability (persist/writebehind.py): per-tick
        # Save-flagged diffs stream to the store off-thread, staged in a
        # crash-safe WAL.  Built from kwargs (not passed in ready-made)
        # so LocalCluster.revive_role's kwargs replay reconstructs the
        # pipeline over the SAME wal dir and recovers queued batches.
        self.persist = None
        self._persist_drain_timeout = float(persist_drain_timeout)
        self._persist_dirty: set = set()
        self._persist_class = None
        self._save_props: set = set()
        self._save_records: set = set()
        if persist_store is not None and persist_wal_dir is not None:
            from ...persist.writebehind import WriteBehindPipeline

            self.persist = WriteBehindPipeline(
                persist_store, persist_wal_dir,
                registry=self.telemetry.registry,
                name=f"game{config.server_id}",
            )
            if self.data_agent is not None:
                self.data_agent.pipeline = self.persist
                self._persist_class = self.data_agent.class_name
                spec = self.kernel.store.spec(self._persist_class)
                for slot in spec.slots.values():
                    p = slot.prop
                    if not p.flag("save"):
                        continue
                    self._save_props.add(p.name)
                    # own subscriber: harvest is independent of which
                    # props the sync spine happens to watch
                    self.kernel.register_property_event(
                        self._persist_class, p.name, self._persist_prop_change
                    )
                    if not (p.public or p.upload):
                        # save-only columns aren't in diff_flags: opt
                        # them into device diff extraction or tick-path
                        # writes would never mark them dirty
                        self.kernel.force_diff_property(
                            self._persist_class, p.name
                        )
                for rname, rs in spec.records.items():
                    if rs.rec.flag("save"):
                        self._save_records.add(rname)
                        self.kernel.register_record_diff(
                            self._persist_class, rname,
                            self._persist_rec_diff,
                        )
                self.kernel.subscribe_record_host(self._persist_rec_host)
        # frame observatory (ISSUE 7): per-frame exclusive stage clock
        # over the served path (tick → harvest → interest → encode →
        # send) + sampled wire trace state.  NF_STAGE_TIMING=1 flips the
        # kernel into honest per-stage device timing; NF_TRACE_SAMPLE=N
        # traces 1-in-N sessions (0 disables).
        self.stage_clock = StageClock(self.telemetry.registry)
        # serving-edge metrics (docs/OBSERVABILITY.md): dispatch count,
        # sessions covered per dispatch, emitted packets, deferred-lane
        # frames (overlap) — the assemble stage histogram itself comes
        # from StageClock ("nf_stage_assemble_seconds")
        sreg = self.telemetry.registry
        self._serve_dispatches = sreg.counter(
            "nf_serve_dispatches_total",
            "batched serve-kernel dispatches (per class, per chunk)",
        )
        self._serve_packets = sreg.counter(
            "nf_serve_packets_total",
            "per-session packets emitted by the batched serve edge",
        )
        self._serve_deferred = sreg.counter(
            "nf_serve_deferred_frames_total",
            "frames whose interest lane was served one tick late (overlap)",
        )
        self._serve_sessions_hist = sreg.histogram(
            "nf_serve_sessions",
            "sessions covered by one batched serve dispatch",
        )
        # the interest table's crowding (docs/OBSERVABILITY.md): what
        # each frame's build counted, and the sizes the breach policy
        # has given the table (`resolved_interest`)
        by_class = ("cls",)
        self._interest_dropped = sreg.counter(
            "nf_interest_dropped_total",
            "rows no client could be shown because they fit neither "
            "level of the interest table", by_class)
        self._interest_gauges = {
            name: sreg.gauge("nf_interest_" + name, help_, by_class)
            for name, help_ in (
                ("hot_cells", "interest cells holding more rows than the "
                              "table's bucket, newest frame"),
                ("cell_rows_max", "rows in the fullest interest cell, "
                                  "newest frame"),
                ("spill_rows", "rows the interest table's second level "
                               "placed, newest frame"),
                ("candidates_max", "the widest visible set any session "
                                   "was served, newest frame"),
                ("spill_cells", "over-full cells the interest table's "
                                "second level holds (0 until a breach "
                                "sized it)"),
                ("spill_depth", "rows a hot interest cell keeps beyond "
                                "the bucket (0 until a breach sized it)"),
            )
        }
        # K-tick train accounting (mirrors kernel.train_* — counted
        # here so bare-kernel benches still track their own ints)
        self._train_dispatches = sreg.counter(
            "nf_train_dispatches_total",
            "K-tick train megadispatches (one scan program per count)",
        )
        self._train_ticks = sreg.counter(
            "nf_train_ticks_total",
            "logical ticks advanced inside train dispatches",
        )
        self._train_fetch_bytes = sreg.counter(
            "nf_train_fetch_bytes_total",
            "stacked [K, ...] summary-lane bytes fetched per train",
        )
        # property fan-out accounting (mirrors kernel.fanout_mask_*)
        self._fanout_mask_fetches = sreg.counter(
            "nf_fanout_mask_fetches_total",
            "diffs read as bit planes for the property fan-out, one per "
            "(class, bank) on a tick that changed a subscribed column "
            "of the bank",
        )
        self._fanout_mask_bytes = sreg.counter(
            "nf_fanout_mask_bytes_total",
            "bytes of bit planes the property fan-out read from the device",
        )
        self._fanout_mask_columns = sreg.counter(
            "nf_fanout_mask_columns_total",
            "subscribed, changed columns the property fan-out unpacked "
            "to row lists",
        )
        self._stage_timing = stage_timing_enabled()
        self.kernel.stage_timing = self._stage_timing
        self._trace_sample = trace_sample_n()
        self._trace_seq = 0
        self._trace_pending: Dict[int, Tuple[int, int]] = {}
        self.trace_sent = 0
        self.trace_acked = 0
        self.last_trace: Optional[dict] = None
        treg = self.telemetry.registry
        self._trace_rtt_hist = treg.histogram(
            "nf_trace_rtt_seconds",
            "frame-trace round trip: encode → client ack received",
        )
        self._trace_relay_hist = treg.histogram(
            "nf_trace_proxy_relay_seconds",
            "proxy in→out relay of sampled frame traces (proxy clock)",
        )

    def _persist_prop_change(self, cname: str, pname: str, rows) -> None:
        self._persist_dirty.update(int(r) for r in rows)

    def _persist_rec_diff(self, cname: str, rname: str, codes) -> None:
        self._persist_dirty.update(int(e) for e in np.nonzero(
            np.any(codes != 0, axis=1))[0])

    def _persist_rec_host(self, cname, rname, op, erows, rec_row, tags) -> None:
        if cname == self._persist_class and rname in self._save_records:
            self._persist_dirty.update(int(e) for e in erows)

    def _persist_harvest(self) -> None:
        """Stage this tick's dirty Save-flagged entities into the
        write-behind queue as one coalesced batch.  Pump-thread only;
        never touches the store (the flusher owns every store call)."""
        tick = self.kernel.tick_count
        rows, self._persist_dirty = self._persist_dirty, set()
        if rows:
            agent = self.data_agent
            host = self.kernel.store._hosts[self._persist_class]
            k = self.kernel
            items = {}
            for r in sorted(rows):
                g = host.row_guid[r] if r < len(host.row_guid) else None
                if g is None:
                    continue  # died this tick; the destroy hook saved it
                key = agent._key_of(g)
                if key is None:
                    continue
                items[key] = snapshot_object(k.store, k.state, g, agent.flags)
            if items:
                self.persist.enqueue(tick, items)
        self.persist.note_tick(tick)
        self.persist.pump()

    def _journal_tap(self, source: int):
        def tap(ev) -> None:
            j = self.journal
            # frame-trace sidecars (TRACE_MSG_IDS) are pure observability
            # and never touch device state: journaling them would make
            # the recorded input stream — and thus replay byte-identity —
            # depend on whether tracing was sampled that run
            if j is not None and ev.msg_id not in TRACE_MSG_IDS:
                j.event(source, ev.kind, ev.conn_id, ev.msg_id, ev.body)
        return tap

    def _journal_pump_counters(self) -> None:
        """Fold the writer's monotonic totals into the registry as
        deltas (counters only go up; the writer is the source of
        truth)."""
        j = self.journal
        vals = (j.bytes_total, j.segments_total, j.ticks_total)
        for counter, new, i in zip(
            (self._jrn_bytes, self._jrn_segments, self._jrn_ticks),
            vals, range(3),
        ):
            d = new - self._jrn_sampled[i]
            if d:
                counter.inc(d)
                self._jrn_sampled[i] = new

    def journal_note(self, **info) -> None:
        """Drop an epoch marker into the journal (chaos seed + link
        budgets, config flips) — no-op when not recording."""
        if self.journal is not None:
            self.journal.note(info)

    def report(self):
        """Heartbeat report, extended with write-behind health: lag +
        degraded ride the ext map to the master's /json and status page
        (the SUSPECT-surfacing leg of the durability story), and a
        degraded store flips the advertised state to BUSY so balancers
        steer new logins elsewhere while the world stays up."""
        if self.persist is not None and self.state in (
                int(ServerState.NORMAL), int(ServerState.BUSY)):
            self.state = (int(ServerState.BUSY) if self.persist.degraded()
                          else int(ServerState.NORMAL))
        r = super().report()
        ext = r.server_info_list_ext
        if ext is None:
            ext = ServerInfoExt()
            r.server_info_list_ext = ext
        if self.persist is not None:
            for k, v in (
                ("persist_lag_ticks", self.persist.lag_ticks()),
                ("persist_queue_depth", self.persist.queue_depth()),
                ("persist_degraded", int(self.persist.degraded())),
                # durable-media locations for the world's failover
                # driver (ISSUE 10): when THIS role dies, the world
                # reconstructs its players' blobs read-only from here
                ("wal_dir", str(self.persist.wal.path)),
            ):
                ext.key.append(k.encode())
                ext.value.append(str(v).encode())
        if self.checkpoint_dir is not None:
            ext.key.append(b"ckpt_dir")
            ext.value.append(str(self.checkpoint_dir).encode())
        # frame-pipeline attribution blob: the master's /pipeline route
        # parses this into the cluster-wide stage waterfall
        ext.key.append(b"pipeline")
        ext.value.append(_json.dumps(self.pipeline_stats()).encode())
        # compiled-cost heartbeat: compact CostBook summary (per-entry
        # compiles/recompiles/flops/bytes + HBM live/peak) — the master's
        # /costbook route aggregates these into the cluster view
        ext.key.append(b"costbook")
        ext.value.append(
            _json.dumps(self.kernel.costbook.summary()).encode())
        # many-worlds occupancy blob: slot totals + per-room placement,
        # surfaced on the master's /json like pipeline/costbook
        if self.rooms is not None:
            ext.key.append(b"rooms")
            ext.value.append(_json.dumps(self.rooms.status()).encode())
        return r

    def pipeline_stats(self) -> dict:
        """Stage waterfall + wire-trace summary for /pipeline and bench."""
        sc = self.stage_clock
        out = {
            "frames": sc.frames,
            "last_tick": sc.last_tick,
            "last_wall_ms": round(sc.last_wall_ns / 1e6, 4),
            "last_ms": {k: round(v / 1e6, 4) for k, v in sc.last.items()},
            "stages": sc.stats(),
            "transport": self.transport_backend,
            # requests that waited for a later round (inbound budget)
            "inbound_backlog_max": self.server.backlog_max,
            "trace": {
                "sample": self._trace_sample,
                "sent": self.trace_sent,
                "acked": self.trace_acked,
                "pending": len(self._trace_pending),
            },
        }
        if self._trace_rtt_hist.count:
            out["trace"]["rtt_p50_ms"] = round(
                self._trace_rtt_hist.percentile(50.0) * 1e3, 4)
            out["trace"]["rtt_p95_ms"] = round(
                self._trace_rtt_hist.percentile(95.0) * 1e3, 4)
        if self._trace_relay_hist.count:
            out["trace"]["relay_p50_ms"] = round(
                self._trace_relay_hist.percentile(50.0) * 1e3, 4)
        return out

    def _install(self) -> None:
        s = self.server
        s.on(MsgID.REQ_ROLE_LIST, self._on_role_list)
        s.on(MsgID.REQ_CREATE_ROLE, self._on_create_role)
        s.on(MsgID.REQ_DELETE_ROLE, self._on_delete_role)
        s.on(MsgID.REQ_ENTER_GAME, self._on_enter_game)
        s.on(MsgID.REQ_LEAVE_GAME, self._on_leave_game)
        s.on(MsgID.REQ_SWAP_SCENE, self._on_swap_scene)
        s.on(MsgID.REQ_MOVE, self._on_move)
        s.on(MsgID.REQ_CHAT, self._on_chat)
        s.on(MsgID.REQ_SKILL_OBJECTX, self._on_skill)
        s.on(MsgID.REQ_SET_FIGHT_HERO, self._on_set_fight_hero)
        s.on(MsgID.REQ_SWITCH_SERVER, self._on_client_switch)
        s.on(MsgID.REQ_ITEM_OBJECT, self._on_use_item)
        s.on(MsgID.WEAR_EQUIP, self._on_wear_equip)
        s.on(MsgID.TAKEOFF_EQUIP, self._on_takeoff_equip)
        s.on(MsgID.REQ_ACCEPT_TASK, self._on_accept_task)
        s.on(MsgID.REQ_COMPLETE_TASK, self._on_complete_task)
        s.on(MsgID.REQ_CREATE_TEAM, self._on_create_team)
        s.on(MsgID.REQ_JOIN_TEAM, self._on_join_team)
        s.on(MsgID.REQ_LEAVE_TEAM, self._on_leave_team)
        s.on(MsgID.REQ_OPRMEMBER_TEAM, self._on_opr_team_member)
        s.on(MsgID.REQ_CREATE_GUILD, self._on_create_guild)
        s.on(MsgID.REQ_JOIN_GUILD, self._on_join_guild)
        s.on(MsgID.REQ_LEAVE_GUILD, self._on_leave_guild)
        s.on(MsgID.REQ_SEARCH_GUILD, self._on_search_guild)
        s.on(MsgID.REQ_CMD_NORMAL, self._on_gm_command)
        s.on(MsgID.REQ_PVP_APPLY_MATCH, self._on_pvp_apply)
        s.on(MsgID.REQ_CREATE_PVP_ECTYPE, self._on_pvp_create_ectype)
        s.on(MsgID.REQ_BUY_FORM_SHOP, self._on_slg_buy)
        s.on(MsgID.REQ_MOVE_BUILD_OBJECT, self._on_slg_move)
        s.on(MsgID.REQ_UP_BUILD_LVL, self._on_slg_upgrade)
        s.on(MsgID.REQ_CREATE_ITEM, self._on_slg_create_item)
        s.on(MsgID.REQ_BUILD_OPERATE, self._on_slg_operate)
        s.on(MsgID.FRAME_TRACE_ACK, self._on_frame_trace_ack)
        s.on_socket_event(self._on_socket)

    def cur_count(self) -> int:
        return len(self.sessions)

    def inbound_budget_seconds(self) -> float:
        """One frame period.  A handler here reads and writes device
        state eagerly (enter-game: ~170 such operations, 0.57 s a session
        on a TPU v5e), so a login burst served in one round would hold
        the tick, the flush and this role's heartbeats for the whole
        burst; bounded, requests wait in arrival order and a round is at
        most a frame period and one handler late."""
        return self.game_world.config.dt

    # ------------------------------------------------------------ sending
    def _send_to(self, idents: Sequence[Ident], conn_id: int, msg_id: int,
                 msg: Message) -> None:
        # "send" stage = envelope encode + transport write; add_ns keeps
        # it exclusive of whichever stage (interest/encode) called us
        with span("stage.send"):
            t0 = _time.perf_counter_ns()
            self.server.send_raw(
                conn_id, int(msg_id), wrap(msg, clients=list(idents))
            )
            self.stage_clock.add_ns("send", _time.perf_counter_ns() - t0)

    # ------------------------------------------------------ wire tracing
    def _emit_frame_traces(self) -> None:
        """End of a flushed frame: send the sampled sessions a FRAME_TRACE
        sidecar.  TCP ordering puts it *behind* the frame's sync traffic
        on the same connection, so the acked round trip upper-bounds the
        frame's true delivery latency."""
        n = self._trace_sample
        for sess in self.sessions.values():
            if sess.ident.index % n:
                continue
            self._trace_seq = (self._trace_seq + 1) & 0xFFFFFFFF
            seq = self._trace_seq
            tick = self.kernel.tick_count
            # one span per sampled sidecar, the finest grain a span has:
            # (tick, seq) joins it with the proxy's and the client's
            with span("trace.emit", tick=tick, seq=seq):
                t_enc = _time.perf_counter_ns()
                ctx = TraceContext(tick=tick,
                                   game_id=self.config.server_id,
                                   seq=seq, t_encode_ns=t_enc)
                self._trace_pending[seq] = (tick, t_enc)
                while len(self._trace_pending) > 4096:  # lost acks
                    self._trace_pending.pop(next(iter(self._trace_pending)))
                base = MsgBase(player_id=sess.ident,
                               msg_data=encode_trace(ctx),
                               player_client_list=[sess.ident])
                self.server.send_raw(
                    sess.conn_id, int(MsgID.FRAME_TRACE), base.encode()
                )
            self.trace_sent += 1

    def _on_frame_trace_ack(self, _conn_id: int, _msg_id: int,
                            body: bytes) -> None:
        """Client echoed the stamped header back: close the loop with
        same-clock deltas only — RTT on the game clock, relay on the
        proxy clock.  Never touches device state (replay identity)."""
        now_ns = _time.perf_counter_ns()
        base = MsgBase.decode(body)
        try:
            ctx = decode_trace(base.msg_data)
        except TraceError:
            return
        if ctx.game_id != self.config.server_id:
            return
        pend = self._trace_pending.pop(ctx.seq, None)
        if pend is None:
            return  # duplicate or aged out
        tick, t_enc = pend
        rtt_s = (now_ns - t_enc) / 1e9
        self._trace_rtt_hist.observe(rtt_s)
        relay_ms = None
        if ctx.proxy_out_ns and ctx.proxy_in_ns:
            relay_s = (ctx.proxy_out_ns - ctx.proxy_in_ns) / 1e9
            self._trace_relay_hist.observe(relay_s)
            relay_ms = round(relay_s * 1e3, 4)
        self.trace_acked += 1
        self.last_trace = {
            "tick": tick,
            "seq": ctx.seq,
            "rtt_ms": round(rtt_s * 1e3, 4),
            "proxy_relay_ms": relay_ms,
        }

    def _send_to_session(self, sess: Session, msg_id: int, msg: Message) -> None:
        self._send_to([sess.ident], sess.conn_id, msg_id, msg)

    def _broadcast(self, target_guids: Sequence[Guid], msg_id: int,
                   msg: Message, exclude: Optional[Guid] = None) -> None:
        """Fan a message out to the sessions of `target_guids`, grouping
        client idents per proxy connection (one envelope per proxy link —
        the multicast list the reference's Transpond expands)."""
        per_conn: Dict[int, List[Ident]] = {}
        for g in target_guids:
            if exclude is not None and g == exclude:
                continue
            key = self._guid_session.get(g)
            if key is None:
                continue
            sess = self.sessions.get(key)
            if sess is not None:
                per_conn.setdefault(sess.conn_id, []).append(sess.ident)
        for conn_id, idents in per_conn.items():
            self._send_to(idents, conn_id, msg_id, msg)

    def _scene_players(self, guid: Guid) -> List[Guid]:
        return self.scene.broadcast_targets(guid, public=True)

    # ------------------------------------------------------------ role CRUD
    def _session_for(self, conn_id: int, base: MsgBase) -> Session:
        key = _ident_key(base.player_id)
        sess = self.sessions.get(key)
        if sess is None:
            sess = Session(ident=base.player_id or Ident(), conn_id=conn_id)
            self.sessions[key] = sess
        sess.conn_id = conn_id
        return sess

    def _get_roles(self, account: str) -> List[RoleLiteInfo]:
        roles = self.roles.get(account)
        if roles is None:
            roles = (self.role_store.load(account)
                     if self.role_store is not None else [])
            self.roles[account] = roles
        return roles

    def _put_roles(self, account: str, roles: List[RoleLiteInfo]) -> None:
        self.roles[account] = roles
        if self.role_store is not None:
            self.role_store.save(account, roles)

    def _on_role_list(self, conn_id: int, _msg_id: int, body: bytes) -> None:
        base, req = unwrap(body, ReqRoleList)
        sess = self._session_for(conn_id, base)
        sess.account = req.account.decode("utf-8", "replace") or sess.account
        ack = AckRoleLiteInfoList(char_data=self._get_roles(sess.account))
        self._send_to_session(sess, MsgID.ACK_ROLE_LIST, ack)

    def _on_create_role(self, conn_id: int, _msg_id: int, body: bytes) -> None:
        base, req = unwrap(body, ReqCreateRole)
        sess = self._session_for(conn_id, base)
        account = req.account.decode("utf-8", "replace") or sess.account
        sess.account = account
        roles = self._get_roles(account)
        name = req.noob_name
        if any(r.noob_name == name for r in roles):
            code = int(EventCode.CHARACTER_EXIST)
        else:
            roles.append(
                RoleLiteInfo(
                    id=guid_ident(self.kernel.store.guids.next()),
                    career=req.career,
                    sex=req.sex,
                    race=req.race,
                    noob_name=name,
                    game_id=req.game_id,
                    role_level=1,
                )
            )
            self._put_roles(account, roles)
            code = int(EventCode.SUCCESS)
        self._send_to_session(
            sess, MsgID.EVENT_RESULT, AckEventResult(event_code=code)
        )
        # the reference replies with the refreshed role list either way
        ack = AckRoleLiteInfoList(char_data=roles)
        self._send_to_session(sess, MsgID.ACK_ROLE_LIST, ack)

    def _on_delete_role(self, conn_id: int, _msg_id: int, body: bytes) -> None:
        base, req = unwrap(body, ReqDeleteRole)
        sess = self._session_for(conn_id, base)
        account = req.account.decode("utf-8", "replace") or sess.account
        remaining = [r for r in self._get_roles(account)
                     if r.noob_name != req.name]
        self._put_roles(account, remaining)
        if self.data_agent is not None:
            name = req.name.decode("utf-8", "replace")
            self.data_agent.delete(f"{account}:{name}")
        self._send_to_session(
            sess, MsgID.ACK_ROLE_LIST,
            AckRoleLiteInfoList(char_data=remaining),
        )

    # ------------------------------------------------------------ enter/leave
    def _on_enter_game(self, conn_id: int, _msg_id: int, body: bytes) -> None:
        base, req = unwrap(body, ReqEnterGameServer)
        sess = self._session_for(conn_id, base)
        sess.account = req.account.decode("utf-8", "replace") or sess.account
        if sess.guid is not None:
            self._despawn(sess)  # re-entry replaces the old avatar
        name = req.name.decode("utf-8", "replace")
        store = self.kernel.store
        if store.live_count("Player") >= store.capacity("Player"):
            # world full: refuse gracefully BEFORE allocating, so no row
            # leaks and the pump keeps serving — the reference answers
            # with an event-result code on every enter-game failure path.
            # Other create failures propagate to the dispatch isolation
            # layer (logged + message dropped).
            self._send_to_session(
                sess,
                MsgID.ACK_ENTER_GAME,
                AckEventResult(event_code=int(EventCode.CHARACTER_NUMOUT)),
            )
            return
        guid = self.kernel.create_object(
            "Player",
            {"Name": name, "Account": sess.account, "GameID": self.config.server_id},
            scene=0,
            group=0,
        )
        sess.guid = guid
        self._guid_session[guid] = _ident_key(sess.ident)
        # stat init: fresh players get level 1 + full refill; returning
        # players keep their loaded Level/HP (the data agent attached the
        # saved blob during CREATE_LOADDATA) and only the derived stats
        # are rebuilt (reference OnObjectLevelEvent → RefreshBaseProperty)
        gw = self.game_world
        loaded = (self.data_agent is not None and sess.account
                  and self.data_agent.exists(f"{sess.account}:{name}"))
        if not loaded:
            self.kernel.set_property(guid, "Level", 1)
        gw.properties.refresh_base_property(guid, gw.property_config)
        gw.properties.recompute_now(guid)
        if not loaded:
            gw.properties.full_hp_mp(guid)
            gw.properties.full_sp(guid)
        # enter-scene pipeline (RequestEnterScene semantics; clone scenes
        # mint a private instance via SceneProcessModule)
        self._enter_scene(guid, self.scene_id)
        ack = AckEventResult(
            event_code=int(EventCode.ENTER_GAME_SUCCESS),
            event_object=guid_ident(guid),
        )
        self._send_to_session(sess, MsgID.ACK_ENTER_GAME, ack)
        self._send_snapshots(sess)
        if self.cross_server_sync:
            self._notify_online(sess, guid, self.scene_id, 0)

    def _notify_online(self, sess: Session, guid: Guid,
                       scene_id: int, group_id: int) -> None:
        """Cross-server online notify + session-bind sidecar (ISSUE 10):
        the world's roster learns the player came online, and its
        failover driver learns everything needed to re-home this session
        — durable save key included — should this role die unasked."""
        from ..wire import RoleOnlineNotify, SessionBindNotify

        ident = guid_ident(guid)
        self.world_link.send_to_all(
            int(MsgID.ACK_ONLINE_NOTIFY),
            wrap(RoleOnlineNotify(), player_id=ident),
        )
        save_key = ""
        if self.data_agent is not None:
            save_key = self.data_agent._key_of(guid) or ""
        bind = SessionBindNotify(
            selfid=ident,
            account=(sess.account or "").encode(),
            name=str(self.kernel.get_property(guid, "Name") or "").encode(),
            client_id=sess.ident,
            scene_id=int(scene_id),
            group_id=int(group_id),
            save_key=save_key.encode(),
            game_id=int(self.config.server_id),
        )
        self.world_link.send_to_all(
            int(MsgID.SESSION_BIND_NOTIFY), wrap(bind)
        )

    def _on_leave_game(self, conn_id: int, _msg_id: int, body: bytes) -> None:
        base, _ = unwrap(body)
        key = _ident_key(base.player_id)
        sess = self.sessions.pop(key, None)
        if sess is not None:
            self._despawn(sess)

    def reset_view(self, sess: Session) -> dict:
        """Forget everything this session's client mirrors: fresh legacy
        seen-dict AND a wiped device seen-state row (batched engine).
        The single chokepoint for every view reset — despawn, switch-out,
        out-of-band destroy, and the lazy first-serve init all route
        here so the two engines can never drift on reset semantics."""
        seen = sess._interest_seen = {}
        self._session_table.reset_view(_ident_key(sess.ident))
        return seen

    def _despawn(self, sess: Session) -> None:
        if sess.guid is None:
            return
        guid = sess.guid
        targets = self._scene_players(guid)
        sess.guid = None
        # the interest seen-state belongs to the AVATAR's view: a fresh
        # client (crash + reconnect) starts with an empty mirror, so a
        # stale seen-state would suppress every stationary entity forever
        self.reset_view(sess)
        self._guid_session.pop(guid, None)
        # PVP hygiene: a queued ticket would ghost-match a gone player,
        # and an unconsumed room entry would leak forever
        pvp = getattr(self.game_world, "pvp", None)
        if pvp is not None:
            pvp.leave_queue(guid)
        for rid, pair in list(self._pvp_rooms.items()):
            if guid in pair:
                del self._pvp_rooms[rid]
                # the surviving fighter must hear the match died, or
                # they wait on a room that can never mint its ectype
                for other in pair:
                    if other == guid:
                        continue
                    key = self._guid_session.get(other)
                    s2 = self.sessions.get(key) if key is not None else None
                    if s2 is not None:
                        from ..wire import AckPVPApplyMatch

                        self._send_to_session(
                            s2, MsgID.ACK_PVP_APPLY_MATCH,
                            AckPVPApplyMatch(nResult=0),  # cancelled
                        )
        if guid in self.kernel.store.guid_map:
            self.kernel.destroy_object(guid)
        leave = AckPlayerLeaveList(object_list=[guid_ident(guid)])
        self._broadcast(targets, MsgID.ACK_OBJECT_LEAVE, leave, exclude=guid)
        if self.cross_server_sync:
            from ..wire import RoleOfflineNotify

            self.world_link.send_to_all(
                int(MsgID.ACK_OFFLINE_NOTIFY),
                wrap(RoleOfflineNotify(), player_id=guid_ident(guid)),
            )

    def _on_socket(self, conn_id: int, kind: int) -> None:
        if kind != EV_DISCONNECTED:
            return
        # a proxy link died: all its clients are gone
        for key, sess in list(self.sessions.items()):
            if sess.conn_id == conn_id:
                self._despawn(sess)
                self.sessions.pop(key, None)

    # ------------------------------------------------------------ snapshots
    def _entry_info(self, guid: Guid) -> PlayerEntryInfo:
        k = self.kernel
        cname, _ = k.store.row_of(guid)
        pos = k.get_property(guid, "Position")
        cfg = ""
        if k.store.spec(cname).has_property("ConfigID"):
            cfg = str(k.get_property(guid, "ConfigID"))
        return PlayerEntryInfo(
            object_guid=guid_ident(guid),
            x=pos[0], y=pos[1], z=pos[2] if len(pos) > 2 else 0.0,
            scene_id=int(k.get_property(guid, "SceneID")),
            class_id=cname.encode(),
            config_id=cfg.encode(),
        )

    def _property_list(self, guid: Guid, include_private: bool) -> ObjectPropertyList:
        """Full property snapshot (OnPropertyEnter: Public to others,
        Public+Private to self) via the shared serializer."""
        pred = (lambda d: d.flag("public") or d.flag("private")) \
            if include_private else (lambda d: d.flag("public"))
        out = serialize_properties(self.kernel.store, self.kernel.state,
                                   guid, pred)
        out.player_id = guid_ident(guid)
        return out

    def _record_list(self, guid: Guid, include_private: bool) -> ObjectRecordList:
        """Record snapshot for the flag-visible records (OnRecordEnter)
        via the shared serializer."""
        pred = (lambda d: d.flag("public") or d.flag("private")) \
            if include_private else (lambda d: d.flag("public"))
        out = serialize_records(self.kernel.store, self.kernel.state,
                                guid, pred)
        out.player_id = guid_ident(guid)
        return out

    def _send_snapshots(self, sess: Session) -> None:
        """Object-entry choreography toward the new client + the rest of
        the group (OnObjectListEnter / OnPropertyEnter / OnRecordEnter)."""
        guid = sess.guid
        visible: List[Guid] = []
        for cname in self.sync_classes:
            visible.extend(
                self.scene.objects_in_group(self.scene_id, 1, cname)
            )
        entry_all = AckPlayerEntryList(
            object_list=[self._entry_info(g) for g in visible]
        )
        self._send_to_session(sess, MsgID.ACK_OBJECT_ENTRY, entry_all)
        for g in visible:
            self._send_to_session(
                sess, MsgID.ACK_OBJECT_PROPERTY_ENTRY,
                self._property_list(g, include_private=(g == guid)),
            )
        self._send_to_session(
            sess, MsgID.ACK_OBJECT_RECORD_ENTRY,
            self._record_list(guid, include_private=True),
        )
        # announce the newcomer to everyone already there
        entry_self = AckPlayerEntryList(object_list=[self._entry_info(guid)])
        others = self._scene_players(guid)
        self._broadcast(others, MsgID.ACK_OBJECT_ENTRY, entry_self, exclude=guid)
        self._broadcast(
            others, MsgID.ACK_OBJECT_PROPERTY_ENTRY,
            self._property_list(guid, include_private=False), exclude=guid,
        )

    # ------------------------------------------------------------ gameplay
    def _enter_scene(self, guid, scene_id: int, group: int = 1) -> int:
        """Enter routed by scene type (NFCSceneProcessModule semantics):
        clone scenes mint a private instance for the enterer, normal
        scenes share `group` (created on first use)."""
        if scene_id not in self.scene.scenes:
            self.scene.create_scene(scene_id)
        sp = getattr(self.game_world, "scene_process", None)
        if sp is not None:
            return sp.enter(guid, scene_id, group)
        if group not in self.scene.scenes[scene_id].groups:
            self.scene.request_group(scene_id, group_id=group)
        self.scene.enter_scene(guid, scene_id, group)
        return group

    def _on_swap_scene(self, conn_id: int, _msg_id: int, body: bytes) -> None:
        base, req = unwrap(body, ReqAckSwapScene)
        sess = self.sessions.get(_ident_key(base.player_id))
        if sess is None or sess.guid is None:
            return
        self._enter_scene(sess.guid, req.scene_id)
        self._send_to_session(sess, MsgID.ACK_SWAP_SCENE, req)

    def _on_move(self, conn_id: int, _msg_id: int, body: bytes) -> None:
        base, req = unwrap(body, ReqAckPlayerMove)
        sess = self.sessions.get(_ident_key(base.player_id))
        if sess is None or sess.guid is None or not req.target_pos:
            return
        p = req.target_pos[0]
        self.kernel.set_property(sess.guid, "Position", (p.x, p.y, p.z))
        req.mover = guid_ident(sess.guid)
        self._broadcast(self._scene_players(sess.guid), MsgID.ACK_MOVE, req)

    def _on_chat(self, conn_id: int, _msg_id: int, body: bytes) -> None:
        base, req = unwrap(body, ReqAckPlayerChat)
        sess = self.sessions.get(_ident_key(base.player_id))
        if sess is None or sess.guid is None:
            return
        req.chat_id = guid_ident(sess.guid)
        self._broadcast(self._scene_players(sess.guid), MsgID.ACK_CHAT, req)

    def _on_skill(self, conn_id: int, _msg_id: int, body: bytes) -> None:
        """Host-path skill resolution (`NFCSkillModule::OnUseSkill`
        HP-damage semantics, `NFCSkillModule.cpp:74-160`); batch AoE lives
        in game/combat.py on device."""
        base, req = unwrap(body, ReqAckUseSkill)
        sess = self.sessions.get(_ident_key(base.player_id))
        if sess is None or sess.guid is None:
            return
        req.user = guid_ident(sess.guid)
        for eff in req.effect_data:
            target = self._guid_of_ident(eff.effect_ident)
            if target is None or target not in self.kernel.store.guid_map:
                continue
            hp = int(self.kernel.get_property(target, "HP"))
            dmg = self.skill_damage
            self.kernel.set_property(target, "HP", max(0, hp - dmg))
            eff.effect_value = dmg
        self._broadcast(self._scene_players(sess.guid), MsgID.ACK_SKILL_OBJECTX, req)

    def _guid_of_ident(self, ident: Optional[Ident]) -> Optional[Guid]:
        if ident is None:
            return None
        return Guid(ident.svrid, ident.index)

    def _on_set_fight_hero(self, conn_id: int, _msg_id: int,
                           body: bytes) -> None:
        """NFCHeroModule::OnSetFightHeroMsg — the hero's record row rides
        heroid.index (heroes are row-identified)."""
        base, req = unwrap(body, ReqSetFightHero)
        sess = self.sessions.get(_ident_key(base.player_id))
        if sess is None or sess.guid is None or req.heroid is None:
            return
        heroes = self.game_world.heroes
        if heroes is not None:
            heroes.set_fight_hero(sess.guid, int(req.heroid.index),
                                  int(req.fight_pos))

    # ---------------------------------------------- middleware handlers
    # reference: NFCItemModule::OnClientUseItem, NFCEquipModule wear /
    # takeoff callbacks, NFCTaskModule::OnClientAcceptTask /
    # OnClientCompeleteTask, NFCTeamModule and the guild handlers.  All
    # degrade to no-ops when the world was assembled without the
    # middleware stack (bench worlds).
    def _mid_session(self, base) -> Optional[Session]:
        sess = self.sessions.get(_ident_key(base.player_id))
        if sess is None or sess.guid is None:
            return None
        return sess

    def _on_use_item(self, conn_id: int, _msg_id: int, body: bytes) -> None:
        base, req = unwrap(body, ReqAckUseItem)
        sess = self._mid_session(base)
        items = self.game_world.items
        if sess is None or items is None or req.item is None:
            return
        config_id = req.item.item_id.decode("utf-8", "replace")
        # row targets are tagged with svrid == 1 (ROW_TARGET_SVRID): row 0
        # is a VALID first record row, and protoc clients always send the
        # required targetid field (zeroed when untargeted), so the index
        # alone cannot discriminate "no target" from "row 0"
        target = (int(req.targetid.index)
                  if (req.targetid is not None
                      and int(req.targetid.svrid) == ROW_TARGET_SVRID)
                  else None)
        if items.use_item(sess.guid, config_id, target=target):
            req.user = guid_ident(sess.guid)
            self._send_to_session(sess, MsgID.ACK_ITEM_OBJECT, req)

    def _on_wear_equip(self, conn_id: int, _msg_id: int, body: bytes) -> None:
        base, req = unwrap(body, ReqWearEquip)
        sess = self._mid_session(base)
        equip = self.game_world.equip
        if sess is None or equip is None or req.equipid is None:
            return
        equip.wear(sess.guid, int(req.equipid.index))

    def _on_takeoff_equip(self, conn_id: int, _msg_id: int,
                          body: bytes) -> None:
        base, req = unwrap(body, TakeOffEquip)
        sess = self._mid_session(base)
        equip = self.game_world.equip
        if sess is None or equip is None or req.equipid is None:
            return
        equip.take_off(sess.guid, int(req.equipid.index))

    def _on_accept_task(self, conn_id: int, _msg_id: int,
                        body: bytes) -> None:
        base, req = unwrap(body, ReqAcceptTask)
        sess = self._mid_session(base)
        tasks = self.game_world.tasks
        if sess is None or tasks is None:
            return
        tasks.accept(sess.guid, req.task_id.decode("utf-8", "replace"))

    def _on_complete_task(self, conn_id: int, _msg_id: int,
                          body: bytes) -> None:
        base, req = unwrap(body, ReqCompeleteTask)
        sess = self._mid_session(base)
        tasks = self.game_world.tasks
        if sess is None or tasks is None:
            return
        tasks.draw_award(sess.guid, req.task_id.decode("utf-8", "replace"))

    # ------------------------------------------------------------- teams
    def _team_info(self, info) -> "TeamInfo":
        k = self.kernel
        members = []
        for m in info.members:
            if m not in k.store.guid_map:
                continue
            members.append(TeammemberInfo(
                player_id=guid_ident(m),
                name=str(k.get_property(m, "Name")).encode(),
                nLevel=int(k.get_property(m, "Level")),
                job=int(k.get_property(m, "Job")),
            ))
        return TeamInfo(
            team_id=guid_ident(info.group_id),
            captain_id=guid_ident(info.leader),
            teammemberInfo=members,
        )

    def _on_create_team(self, conn_id: int, _msg_id: int,
                        body: bytes) -> None:
        base, _req = unwrap(body, ReqAckCreateTeam)
        sess = self._mid_session(base)
        team = self.game_world.team
        if sess is None or team is None:
            return
        tid = team.create_team(sess.guid)
        if tid is None:
            return
        info = team.team_of(sess.guid)
        self._send_to_session(
            sess, MsgID.ACK_CREATE_TEAM,
            ReqAckCreateTeam(team_id=guid_ident(tid),
                             xTeamInfo=self._team_info(info)),
        )

    def _on_join_team(self, conn_id: int, _msg_id: int, body: bytes) -> None:
        base, req = unwrap(body, ReqAckJoinTeam)
        sess = self._mid_session(base)
        team = self.game_world.team
        if sess is None or team is None or req.team_id is None:
            return
        tid = self._guid_of_ident(req.team_id)
        if not team.join(tid, sess.guid):
            return
        info = team.team_of(sess.guid)
        ack = ReqAckJoinTeam(team_id=req.team_id,
                             xTeamInfo=self._team_info(info))
        # the whole roster hears about the new member
        self._broadcast(list(info.members), MsgID.ACK_JOIN_TEAM, ack)

    def _on_leave_team(self, conn_id: int, _msg_id: int,
                       body: bytes) -> None:
        base, req = unwrap(body, ReqAckLeaveTeam)
        sess = self._mid_session(base)
        team = self.game_world.team
        if sess is None or team is None:
            return
        info = team.team_of(sess.guid)
        if info is None or not team.leave(sess.guid):
            return
        ack = ReqAckLeaveTeam(team_id=guid_ident(info.group_id))
        self._broadcast(list(info.members) + [sess.guid],
                        MsgID.ACK_LEAVE_TEAM, ack)

    def _on_opr_team_member(self, conn_id: int, _msg_id: int,
                            body: bytes) -> None:
        """Captain member ops — KICK/KICKOUT implemented (the other
        EGTeamMemberOprType values are fight-position bookkeeping the
        line-up record owns here)."""
        base, req = unwrap(body, ReqAckOprTeamMember)
        sess = self._mid_session(base)
        team = self.game_world.team
        if sess is None or team is None or req.member_id is None:
            return
        if int(req.type) not in (2, 8):  # EGAT_KICK / EGAT_KICKOUT
            return
        info = team.team_of(sess.guid)
        if info is None or info.leader != sess.guid:
            return  # only the captain operates members
        member = self._guid_of_ident(req.member_id)
        if member == sess.guid or member not in info.members:
            return
        team.leave(member)
        ack = ReqAckOprTeamMember(team_id=guid_ident(info.group_id),
                                  member_id=req.member_id, type=req.type,
                                  xTeamInfo=self._team_info(info))
        self._broadcast(list(info.members) + [member],
                        MsgID.ACK_OPRMEMBER_TEAM, ack)

    # ------------------------------------------------------------ guilds
    def _on_create_guild(self, conn_id: int, _msg_id: int,
                         body: bytes) -> None:
        base, req = unwrap(body, ReqAckCreateGuild)
        sess = self._mid_session(base)
        guilds = self.game_world.guilds
        if sess is None or guilds is None:
            return
        name = req.guild_name.decode("utf-8", "replace")
        gid = guilds.create_guild(sess.guid, name)
        if gid is None:
            return
        self._send_to_session(
            sess, MsgID.ACK_CREATE_GUILD,
            ReqAckCreateGuild(guild_id=guid_ident(gid),
                              guild_name=req.guild_name),
        )

    def _on_join_guild(self, conn_id: int, _msg_id: int,
                       body: bytes) -> None:
        base, req = unwrap(body, ReqAckJoinGuild)
        sess = self._mid_session(base)
        guilds = self.game_world.guilds
        if sess is None or guilds is None:
            return
        name = req.guild_name.decode("utf-8", "replace")
        info = guilds.find_by_name(name)
        if info is None or not guilds.join(info.group_id, sess.guid):
            return
        self._send_to_session(
            sess, MsgID.ACK_JOIN_GUILD,
            ReqAckJoinGuild(guild_id=guid_ident(info.group_id),
                            guild_name=req.guild_name),
        )

    def _on_leave_guild(self, conn_id: int, _msg_id: int,
                        body: bytes) -> None:
        base, req = unwrap(body, ReqAckLeaveGuild)
        sess = self._mid_session(base)
        guilds = self.game_world.guilds
        if sess is None or guilds is None:
            return
        info = guilds.guild_of(sess.guid)
        if info is None or not guilds.leave(sess.guid):
            return
        self._send_to_session(
            sess, MsgID.ACK_LEAVE_GUILD,
            ReqAckLeaveGuild(guild_id=guid_ident(info.group_id),
                             guild_name=info.name.encode()),
        )

    def _on_search_guild(self, conn_id: int, _msg_id: int,
                         body: bytes) -> None:
        base, req = unwrap(body, ReqSearchGuild)
        sess = self._mid_session(base)
        guilds = self.game_world.guilds
        if sess is None or guilds is None:
            return
        needle = req.guild_name.decode("utf-8", "replace").lower()
        out = []
        for info in guilds.guilds.values():
            if needle and needle not in info.name.lower():
                continue
            out.append(SearchGuildObject(
                guild_ID=guid_ident(info.group_id),
                guild_name=info.name.encode(),
                guild_member_count=len(info.members),
                guild_member_max_count=info.capacity,
            ))
        self._send_to_session(sess, MsgID.ACK_SEARCH_GUILD,
                              AckSearchGuild(guild_list=out))

    # --------------------------------------------------------- GM + PVP
    def _on_gm_command(self, conn_id: int, _msg_id: int,
                       body: bytes) -> None:
        """EGMI_REQ_CMD_NORMAL (NFCGmModule::OnGMNormalProcess):
        ReqCommand's typed EGameCommandType mapped onto GmModule's
        chat-command grammar, so the GMLevel gate applies identically."""
        from ..wire import ReqCommand

        base, req = unwrap(body, ReqCommand)
        sess = self._mid_session(base)
        gm = self.game_world.gm
        if sess is None or gm is None:
            return
        k = self.kernel
        sval = (req.command_str_value or b"").decode("utf-8", "replace")
        ival = int(req.command_value_int or 0)
        cmd = int(req.command_id)
        if cmd == 0:  # EGCT_MODIY_PROPERTY: SET the named int property
            if int(k.get_property(sess.guid, "GMLevel")) < gm.min_gm_level:
                return
            spec = k.store.spec("Player")
            if sval and spec.has_property(sval) \
                    and spec.slot(sval).prop.type == DataType.INT:
                k.set_property(sess.guid, sval, ival)
            return
        text = {
            1: f"/item {sval} {ival or 1}",  # EGCT_MODIY_ITEM
            3: f"/exp {ival}",  # EGCT_ADD_ROLE_EXP
        }.get(cmd)
        if text is not None:
            gm.handle_command(sess.guid, text)

    def _on_pvp_apply(self, conn_id: int, _msg_id: int, body: bytes) -> None:
        """EGMI_REQ_PVPAPPLYMACTCH (NFCGSPVPMatchModule shape): queue the
        player; when the score-window pairing matches two, BOTH get an
        ACK with the room (red/blue) and the role tracks it for the
        ectype step."""
        from ..wire import AckPVPApplyMatch, PVPRoomInfo, ReqPVPApplyMatch

        base, req = unwrap(body, ReqPVPApplyMatch)
        sess = self._mid_session(base)
        pvp = self.game_world.pvp
        if sess is None or pvp is None:
            return
        score = int(self.kernel.get_property(sess.guid, "Level")
                    if req.score is None else req.score)  # 0 is a real rating
        if not pvp.join_queue(sess.guid, score, mode=int(req.nPVPMode)):
            # already queued: re-apply means switch (new mode/score wins)
            pvp.leave_queue(sess.guid)
            pvp.join_queue(sess.guid, score, mode=int(req.nPVPMode))
        for ta, tb in pvp.match_once_tickets():
            red, blue = ta.player, tb.player
            room_id = self.kernel.store.guids.next()
            room = PVPRoomInfo(
                nCellStatus=0,
                RoomID=guid_ident(room_id),
                # the PAIR's queue mode — window-widening can match a
                # pair during someone else's request
                nPVPMode=ta.mode,
                MaxPalyer=2,
                xRedPlayer=[guid_ident(red)],
                xBluePlayer=[guid_ident(blue)],
                serverid=self.config.server_id,
            )
            self._pvp_rooms[(room_id.head, room_id.data)] = (red, blue)
            ack = AckPVPApplyMatch(xRoomInfo=room,
                                   ApplyType=int(req.ApplyType), nResult=1)
            for g in (red, blue):
                key = self._guid_session.get(g)
                s2 = self.sessions.get(key) if key is not None else None
                if s2 is not None:
                    ack.self_id = guid_ident(g)
                    self._send_to_session(s2, MsgID.ACK_PVP_APPLY_MATCH, ack)

    def _on_pvp_create_ectype(self, conn_id: int, _msg_id: int,
                              body: bytes) -> None:
        """EGMI_REQ_CREATEPVPECTYPE: mint the PVP instance — a CLONE
        scene group both fighters enter (the reference pulls both sides
        into the room's ectype scene)."""
        from ..wire import AckCreatePVPEctype, ReqCreatePVPEctype

        base, req = unwrap(body, ReqCreatePVPEctype)
        sess = self._mid_session(base)
        if sess is None or req.xRoomInfo is None or req.xRoomInfo.RoomID is None:
            return
        rid = (req.xRoomInfo.RoomID.svrid, req.xRoomInfo.RoomID.index)
        pair = self._pvp_rooms.get(rid)
        if pair is None or sess.guid not in pair:
            return  # unknown room, or a NON-participant: room stays live
        del self._pvp_rooms[rid]
        scene_id = int(req.xRoomInfo.SceneID or
                       self.kernel.get_property(sess.guid, "SceneID"))
        if scene_id not in self.scene.scenes:
            self.scene.create_scene(scene_id)
        # ONE shared instance for both fighters (scene_process.enter
        # would mint a private clone group per enterer)
        group = self.scene.request_group(scene_id)
        for g in pair:
            if g in self.kernel.store.guid_map:
                self.scene.enter_scene(g, scene_id, group)
        req.xRoomInfo.SceneID = scene_id
        req.xRoomInfo.groupID = group
        ack = AckCreatePVPEctype(xRoomInfo=req.xRoomInfo)
        for g in pair:
            key = self._guid_session.get(g)
            s2 = self.sessions.get(key) if key is not None else None
            if s2 is not None:
                ack.self_id = guid_ident(g)  # per-recipient, like apply
                self._send_to_session(s2, MsgID.ACK_CREATE_PVP_ECTYPE, ack)

    # ---------------------------------------------- cross-server switch
    # Reference NFCGSSwichServerModule.cpp: game A serializes nothing and
    # relies on a shared DB; here the player's save-flag snapshot rides a
    # SWITCH_SERVER_DATA companion message, so the re-home works without
    # one.  Flow: A.switch_server -> world -> B (_on_switch_in: create,
    # apply blob, enter scene, tell the proxy to re-route, ack) ->
    # world -> A (_on_switch_ack: drop session, destroy local copy).
    def switch_server(self, guid: Guid, target_server_id: int,
                      scene_id: int = 1, group: int = 0) -> bool:
        """ChangeServer (NFCGSSwichServerModule.cpp:49-77)."""
        from ...persist.codec import snapshot_object
        from ...persist.rowblob import frame_blob

        key = self._guid_session.get(guid)
        sess = self.sessions.get(key) if key is not None else None
        if sess is None or target_server_id == self.config.server_id:
            return False
        k = self.kernel
        # CRC-framed (persist/rowblob.py) so the target detects a blob
        # torn in transit before the codec ever parses it — the same
        # row-serialization story the on-mesh migration shares
        blob = frame_blob(snapshot_object(k.store, k.state, guid))
        ident = guid_ident(guid)
        data = SwitchServerData(
            selfid=ident,
            account=(sess.account or "").encode(),
            name=str(k.get_property(guid, "Name")).encode(),
            blob=blob,
            target_serverid=target_server_id,
        )
        req = ReqSwitchServer(
            selfid=ident,
            self_serverid=self.config.server_id,
            target_serverid=target_server_id,
            gate_serverid=0,  # proxy routing is by client ident here
            scene_id=scene_id,
            client_id=sess.ident,
            group_id=group,
        )
        # both messages MUST ride the same world link (suit-hash by the
        # player) — DATA arriving after REQ on a different link would
        # fail the switch silently
        suit = str(guid)
        self.world_link.send_by_suit(suit, int(MsgID.SWITCH_SERVER_DATA),
                                     wrap(data))
        self.world_link.send_by_suit(suit, int(MsgID.REQ_SWITCH_SERVER),
                                     wrap(req))
        return True

    def _on_client_switch(self, conn_id: int, _msg_id: int,
                          body: bytes) -> None:
        """Client-initiated switch (OnClientReqSwichServer)."""
        base, req = unwrap(body, ReqSwitchServer)
        sess = self.sessions.get(_ident_key(base.player_id))
        if sess is None or sess.guid is None:
            return
        self.switch_server(sess.guid, int(req.target_serverid),
                           int(req.scene_id), int(req.group_id))

    SWITCH_BLOB_TTL_S = 30.0

    def _on_switch_data(self, _sid: int, _msg_id: int, body: bytes) -> None:
        _, data = unwrap(body, SwitchServerData)
        if int(data.target_serverid) != self.config.server_id:
            return
        # sweep expired staged blobs (a world crash between DATA and REQ
        # must not leak entries forever)
        now = _time.monotonic()
        self._switch_blobs = {
            k: (d, t) for k, (d, t) in self._switch_blobs.items()
            if now - t < self.SWITCH_BLOB_TTL_S
        }
        self._switch_blobs[_ident_key(data.selfid)] = (data, now)

    def _on_switch_in(self, _sid: int, _msg_id: int, body: bytes) -> None:
        """Target side (OnReqSwichServer,
        NFCGSSwichServerModule.cpp:96-148): recreate the player from the
        blob, enter the scene, bind the client, re-route the proxy, ack.

        Hardened for supervised failover (ISSUE 10): a duplicate REQ
        re-acks idempotently, a full Player store answers
        ACK_SWITCH_REFUSED (BUSY) instead of half-admitting, and a blob
        torn in transit destroys the half-built object and refuses —
        the driver retries another survivor in every refusal case."""
        from ...persist.codec import apply_snapshot
        from ...persist.rowblob import unframe_blob
        from ..failover import REFUSE_BAD_BLOB, REFUSE_BUSY
        _, req = unwrap(body, ReqSwitchServer)
        if int(req.target_serverid) != self.config.server_id:
            return
        if req.client_id is None or req.selfid is None:
            return
        ckey = _ident_key(req.client_id)
        staged = self._switch_blobs.pop(_ident_key(req.selfid), None)
        if staged is None:
            # duplicate REQ (dup'd link, or a failover re-stage racing
            # the first ack): if this client already owns a live avatar
            # here, repeat the re-route + ack instead of going silent —
            # the world-side driver needs the (possibly lost) ack
            sess = self.sessions.get(ckey)
            if sess is not None and sess.guid is not None:
                self._switch_accept(req, sess)
            return
        data = staged[0]
        k = self.kernel
        store = k.store
        if store.live_count("Player") >= store.capacity("Player"):
            # graceful degradation: no capacity for the refugee — refuse
            # BEFORE allocating so the driver can try another survivor
            self._switch_refuse(req, REFUSE_BUSY)
            return
        guid = k.create_object(
            "Player",
            {
                "Account": data.account.decode("utf-8", "replace"),
                "Name": data.name.decode("utf-8", "replace"),
                "GameID": self.config.server_id,
            },
            scene=int(req.scene_id), group=int(req.group_id),
        )
        if data.blob:
            try:
                # unframe validates CRC/length fail-closed; a legacy
                # (unframed) blob passes through to the codec unchanged
                k.state = apply_snapshot(k.store, k.state, guid,
                                         unframe_blob(data.blob))
            except Exception:
                # torn blob: k.state only mutates on success, so a clean
                # destroy admits nothing half-applied
                if guid in k.store.guid_map:
                    k.destroy_object(guid)
                self._switch_refuse(req, REFUSE_BAD_BLOB)
                return
        k.state = k.store.set_property(k.state, guid, "GameID",
                                       self.config.server_id)
        # bind the client session; the transport conn resolves to the
        # proxy link (single-proxy fast path) and self-corrects on the
        # client's first routed message (_session_for)
        sess = self.sessions.get(ckey)
        if sess is None:
            sess = Session(ident=req.client_id, conn_id=-1)
            self.sessions[ckey] = sess
        sess.account = data.account.decode("utf-8", "replace")
        sess.guid = guid
        self._guid_session[guid] = ckey
        self._enter_scene(guid, int(req.scene_id),
                          group=int(req.group_id) or 1)
        self._switch_accept(req, sess)
        if self.cross_server_sync:
            # adopted players rejoin the roster under THIS game id, so a
            # second failure can re-home them again (roster continuity)
            self._notify_online(sess, guid, int(req.scene_id),
                                int(req.group_id))

    def _switch_accept(self, req, sess: Session) -> None:
        """Re-route the proxy binding and ack the switch — shared by the
        first admit and the duplicate-REQ idempotent repeat."""
        proxy_conns = list(self.server.conn_tags)
        if len(proxy_conns) == 1:
            sess.conn_id = proxy_conns[0]
        # proxy re-route: every proxy link gets the req; the one owning
        # the client ident re-points it at this server
        for conn in proxy_conns:
            self.server.send_raw(conn, int(MsgID.REQ_SWITCH_SERVER),
                                 wrap(req, clients=[req.client_id]))
        ack = AckSwitchServer(
            selfid=req.selfid,
            self_serverid=req.self_serverid,
            target_serverid=req.target_serverid,
            gate_serverid=req.gate_serverid,
        )
        self.world_link.send_to_all(int(MsgID.ACK_SWITCH_SERVER), wrap(ack))

    def _switch_refuse(self, req, result: int) -> None:
        from ..wire import SwitchRefused

        self.world_link.send_to_all(
            int(MsgID.ACK_SWITCH_REFUSED),
            wrap(SwitchRefused(
                selfid=req.selfid,
                self_serverid=int(req.self_serverid),
                target_serverid=int(req.target_serverid),
                result=int(result),
            )),
        )

    def _on_switch_ack(self, _sid: int, _msg_id: int, body: bytes) -> None:
        """Origin side (OnAckSwichServer): the target owns the player
        now — drop the session binding and the local object."""
        _, ack = unwrap(body, AckSwitchServer)
        if int(ack.self_serverid) != self.config.server_id:
            return
        if ack.selfid is None:
            return
        guid = Guid(ack.selfid.svrid, ack.selfid.index)
        key = self._guid_session.pop(guid, None)
        if key is not None:
            sess = self.sessions.pop(key, None)
            if sess is not None:
                sess.guid = None
                self.reset_view(sess)
        if guid in self.kernel.store.guid_map:
            self.kernel.destroy_object(guid)

    # ------------------------------------------------------------ SLG city
    # reference handlers: NFCSLGShopModule::OnSLGClienBuyItem and
    # NFCSLGBuildingModule::OnSLGClienMoveObject/UpgradeBuilding/CreateItem
    def _slg_session(self, base) -> Optional[Session]:
        sess = self.sessions.get(_ident_key(base.player_id))
        if sess is None or sess.guid is None:
            return None
        if self.game_world.slg_building is None:
            return None  # world assembled without the middleware stack
        return sess

    def _on_slg_buy(self, conn_id: int, _msg_id: int, body: bytes) -> None:
        from ..wire_families import ReqAckBuyObjectFormShop

        base, req = unwrap(body, ReqAckBuyObjectFormShop)
        sess = self._slg_session(base)
        if sess is None:
            return
        shop_id = req.config_id.decode("utf-8", "replace")
        if self.game_world.slg_shop.buy(sess.guid, shop_id,
                                        req.x, req.y, req.z):
            self._send_to_session(sess, MsgID.ACK_BUY_FORM_SHOP, req)

    def _on_slg_move(self, conn_id: int, _msg_id: int, body: bytes) -> None:
        from ..wire_families import ReqAckMoveBuildObject

        base, req = unwrap(body, ReqAckMoveBuildObject)
        sess = self._slg_session(base)
        if sess is None or req.row is None:
            return
        if self.game_world.slg_building.move(sess.guid, int(req.row),
                                             req.x, req.y, req.z):
            self._send_to_session(sess, MsgID.ACK_MOVE_BUILD_OBJECT, req)

    def _on_slg_upgrade(self, conn_id: int, _msg_id: int, body: bytes) -> None:
        from ..wire_families import ReqUpBuildLv

        base, req = unwrap(body, ReqUpBuildLv)
        sess = self._slg_session(base)
        if sess is None or req.row is None:
            return
        self.game_world.slg_building.upgrade(sess.guid, int(req.row))

    def _on_slg_create_item(self, conn_id: int, _msg_id: int,
                            body: bytes) -> None:
        from ..wire_families import ReqCreateItem

        base, req = unwrap(body, ReqCreateItem)
        sess = self._slg_session(base)
        if sess is None or req.row is None:
            return
        self.game_world.slg_building.produce(
            sess.guid, int(req.row),
            req.config_id.decode("utf-8", "replace"), int(req.count) or 1,
        )

    def _on_slg_operate(self, conn_id: int, _msg_id: int, body: bytes) -> None:
        from ..wire_families import ReqBuildOperate, SLGFuncType

        base, req = unwrap(body, ReqBuildOperate)
        sess = self._slg_session(base)
        if sess is None or req.row is None:
            return
        b = self.game_world.slg_building
        ft = int(req.functype)
        collect = {
            int(SLGFuncType.COLLECT_GOLD): "Gold",
            int(SLGFuncType.COLLECT_STONE): "Stone",
            int(SLGFuncType.COLLECT_STEEL): "Steel",
            int(SLGFuncType.COLLECT_DIAMOND): "Diamond",
        }.get(ft)
        if collect is not None:
            b.collect(sess.guid, int(req.row), collect)
            return
        fn = {
            int(SLGFuncType.BOOST): b.boost,
            int(SLGFuncType.LVLUP): b.upgrade,
            int(SLGFuncType.CANCEL): b.cancel,
        }.get(ft)
        if fn is not None:
            fn(sess.guid, int(req.row))

    # ------------------------------------------------------------ tick + sync
    def _pump(self, now: float) -> None:
        super()._pump(now)
        pm = self.game_world.pm
        sc = self.stage_clock
        tick_due = now - self._last_tick >= self.game_world.config.dt
        # one stage-clock frame spans tick + flush of this pump pass; a
        # flush can also fire alone (host writes between ticks)
        framed = tick_due or bool(self._changed or self._rec_changed
                                  or self._interest_dirty
                                  or self._serve_pending)
        if framed:
            # the frame is named by the tick it serves (the count its
            # FRAME_TRACE sidecars will carry), so every span of one
            # served frame, on the wire too, shares one number
            sc.frame_begin(self.kernel.tick_count
                           + ((self.tick_train or 1) if tick_due else 0))
        flushed = False
        # overlap mode: interest lanes deferred by the last flush, to be
        # served against THIS frame's pre-tick snapshot (sync_classes
        # order, same as a flush)
        pend_classes: List[str] = []
        if self._serve_pending:
            pend_classes = [
                cn for cn in self.sync_classes if cn in self._serve_pending
            ]
            self._serve_pending.clear()
        train_outs = None
        if tick_due:
            self._last_tick = now
            ticks_this_frame = self.tick_train or 1
            with sc.stage("tick"):
                t0 = _time.perf_counter()
                pm.execute_modules()
                f0, fb0, fc0 = (self.kernel.fanout_mask_fetches,
                                self.kernel.fanout_mask_bytes,
                                self.kernel.fanout_mask_columns)
                if pend_classes:
                    # double-buffered serve: fetch the deferred lanes'
                    # deltas from the pre-tick state (the donated buffers
                    # die at dispatch), start the device tick, and do all
                    # host assembly/encode/send while the device runs
                    with sc.stage("interest"):
                        pend = [
                            d for d in (
                                self._serve_pos_collect(cn)
                                for cn in pend_classes
                            ) if d is not None
                        ]
                    raw = self.kernel.tick_begin()
                    self._serve_deferred.inc()
                    with sc.stage("assemble"):
                        for d in pend:
                            self._serve_pos_emit(d)
                    self.kernel.tick_finish(raw)
                elif self.tick_train:
                    # one K-frame megadispatch; per-frame host effects
                    # (events, diffs, tick-exact deaths, counters) fan
                    # out in order from the stacked lanes
                    d0, t0k, b0 = (self.kernel.train_dispatches,
                                   self.kernel.train_ticks,
                                   self.kernel.train_fetch_bytes)
                    train_outs = self.kernel.train(self.tick_train)
                    self._train_dispatches.inc(
                        self.kernel.train_dispatches - d0)
                    self._train_ticks.inc(self.kernel.train_ticks - t0k)
                    self._train_fetch_bytes.inc(
                        self.kernel.train_fetch_bytes - b0)
                else:
                    self.kernel.tick()
                self._fanout_mask_fetches.inc(
                    self.kernel.fanout_mask_fetches - f0)
                self._fanout_mask_bytes.inc(
                    self.kernel.fanout_mask_bytes - fb0)
                self._fanout_mask_columns.inc(
                    self.kernel.fanout_mask_columns - fc0)
                if self.rooms is not None:
                    # the attached fleet keeps the world's clock: one
                    # vmapped frame for every room slot per world tick
                    for _ in range(ticks_this_frame):
                        self.rooms.tick()
                pm.frame += ticks_this_frame
                # per-tick latency even under trains: one train frame is
                # K ticks of device work behind one dispatch
                self._tick_hist.observe(
                    (_time.perf_counter() - t0) / ticks_this_frame)
            if ticks_this_frame > 1:
                # nf_stage_tick_seconds stays a per-tick distribution
                # across NF_TICK_TRAIN settings (waterfall stays exact)
                sc.set_scale("tick", ticks_this_frame)
            if self.elastic is not None:
                # advance any in-flight grow/drain; when one completes,
                # force-reset exactly the sessions whose seen-state
                # intersects the rows the reshard actually moved
                with sc.stage("reshard"):
                    moved = self.elastic.poll()
                if moved:
                    self._reset_views_for_moved(moved)
            if self.journal is not None:
                # closes this tick's input window; the digest rode the
                # summary fetch the tick already paid for.  A train
                # writes one mark PER stacked frame from the in-lane
                # tick/digest stamps — replay runs one real tick per
                # mark and must compare like for like.
                if train_outs is not None:
                    for o in train_outs:
                        self.journal.tick_mark(
                            o.counters.get("tick", self.kernel.tick_count),
                            o.counters.get("state_digest", 0),
                        )
                else:
                    self.journal.tick_mark(
                        self.kernel.tick_count,
                        self.kernel.last_counters.get("state_digest", 0),
                    )
                self._journal_pump_counters()
            if self.persist is not None:
                # stage this tick's dirty set; all store I/O stays on
                # the flusher thread (the smoke asserts the tick never
                # blocks even with injected store latency)
                self._persist_harvest()
        elif pend_classes:
            # ticks stopped (idle pump): drain the deferred lanes
            # synchronously so staleness stays bounded by pump latency
            with self.telemetry.tracer.span("game.flush"):
                with sc.stage("interest"):
                    for cn in pend_classes:
                        self._send_interest_pos_batched(cn)
                flushed = True
        # _interest_dirty alone must also trigger a flush: a destroy with
        # no property diff still changes visible sets (gone lists)
        if self._changed or self._rec_changed or self._interest_dirty:
            with self.telemetry.tracer.span("game.flush"):
                if self.sessions:
                    self._flush_changes()
                    flushed = True
                else:
                    self._changed.clear()
                    self._rec_changed.clear()
                    self._interest_dirty.clear()
        if framed:
            sc.frame_end()
            if flushed and self._trace_sample > 0:
                self._emit_frame_traces()
        if tick_due:
            # periodic HBM census: live/peak device bytes sampled in-band
            # (scrape-time sampling alone misses peaks between scrapes)
            from ...telemetry.costbook import HBM_SAMPLE_FRAMES

            if self.kernel.tick_count % HBM_SAMPLE_FRAMES == 0:
                self.kernel.costbook.hbm_sample()
        # periodic autosave: device-side deaths free the row before any
        # BEFORE_DESTROY hook can run, so the blob must already be fresh
        if (self.data_agent is not None
                and now - self._last_autosave >= self.autosave_seconds):
            self._last_autosave = now
            for sess in self.sessions.values():
                if sess.guid is not None and sess.guid in self.kernel.store.guid_map:
                    self.data_agent.save(sess.guid)
        # periodic whole-world checkpoint (atomic rename; see
        # persist/checkpoint.py) — the resume path in __init__ restores
        # the latest one after a crash
        if (self.checkpoint_dir is not None
                and now - self._last_checkpoint >= self.checkpoint_seconds):
            self._last_checkpoint = now
            self.checkpoint_now()

    # ------------------------------------------------------- elastic mesh
    @property
    def elastic(self):
        """The world's grow/drain driver (parallel/elastic.py), or None
        for a single-device world.  Read through the world each time so
        a revive that swaps the world swaps the driver with it."""
        return getattr(self.game_world, "elastic", None)

    def grow_mesh(self, n_devices: int) -> None:
        """Expand the serving mesh; the reshard and spatial rebalance
        run inside subsequent ticks' ``reshard`` stage."""
        el = self.elastic
        if el is None:
            raise RuntimeError(f"{self.config.name}: world is not sharded")
        el.begin_grow(int(n_devices))

    def drain_device(self, device_index: int) -> None:
        """Evict one mesh device via the budgeted row exodus, then
        shrink — driven tick-by-tick from the ``reshard`` stage."""
        el = self.elastic
        if el is None:
            raise RuntimeError(f"{self.config.name}: world is not sharded")
        el.begin_drain(int(device_index))

    # ------------------------------------------------------- many worlds
    def attach_rooms(self, directory) -> None:
        """Host a many-worlds RoomDirectory (parallel/rooms.py) beside
        the single world: the pump ticks it once per world tick, inside
        the tick stage (so nobody else may), room status rides the
        heartbeat ext and the room churn verbs below become
        drill-addressable."""
        self.rooms = directory

    def _rooms_or_raise(self):
        if self.rooms is None:
            raise RuntimeError(
                f"{self.config.name}: no RoomDirectory attached")
        return self.rooms

    def create_room(self, seed: Optional[int] = None,
                    room_id: Optional[int] = None,
                    control: bool = False) -> int:
        return self._rooms_or_raise().create_room(
            seed=seed, room_id=room_id, control=control)

    def destroy_room(self, room_id: int) -> int:
        """Free the room's slot and release every session routed to it
        (same reset discipline as a completed reshard: the seen-state
        wipe is lazy, the routing column clears now)."""
        d = self._rooms_or_raise()
        slot = d.destroy_room(room_id)
        table = self._session_table
        if table is not None:
            for key in table.sessions_in_room(room_id):
                table.release(key)
        return slot

    def rehome_room(self, room_id: int):
        """Move a room to another slot/device; sessions keep their
        routing (the room id is stable — only its slot changed), but
        their views reset so the next serve pass resends from the
        re-homed state."""
        d = self._rooms_or_raise()
        src_dst = d.rehome_room(room_id)
        table = self._session_table
        if table is not None:
            for key in table.sessions_in_room(room_id):
                table.reset_view(key)
        return src_dst

    def _reset_views_for_moved(self, moved: Dict[str, np.ndarray]) -> None:
        """Force reset_view for sessions whose seen-state references rows
        a completed reshard moved — and ONLY those.  The batched engine
        intersects per-slot SeenTable rows exactly; the legacy engine's
        per-session seen dicts carry no row index, so it conservatively
        resets every session with a non-empty mirror."""
        from ..serving import sessions_seeing_rows

        affected = set()
        for cname, rows in moved.items():
            if len(rows) == 0:
                continue
            if self.serve_batch:
                affected.update(
                    sessions_seeing_rows(self._session_table, cname, rows))
            else:
                affected.update(
                    k for k, s in self.sessions.items()
                    if getattr(s, "_interest_seen", None))
        count = 0
        for key in affected:
            sess = self.sessions.get(key)
            if sess is not None:
                self.reset_view(sess)
                count += 1
        if count:
            self._reshard_resets.inc(count)

    def checkpoint_now(self):
        """Write one atomic whole-world checkpoint; returns its path."""
        self.game_world.save(self.checkpoint_dir)
        self._ckpt_counter.inc()
        if self.journal is not None:
            # durability point: fsync the journal at the checkpoint mark
            # so the (checkpoint, journal-suffix) pair on disk is always
            # a recoverable replay basis
            self.journal.checkpoint_mark(self.kernel.tick_count)
            self._journal_pump_counters()
        if self.persist is not None:
            # same durability point for the write-behind WAL: after this
            # fsync the newest (checkpoint, WAL suffix) pair on disk is
            # mutually recoverable
            self.persist.barrier(self.kernel.tick_count)
        return self.checkpoint_dir

    def kill(self) -> None:
        """Crash semantics (ISSUE 10 failover drills): tear the sockets
        down WITHOUT the graceful drain — no session saves, no persist
        flush, the WAL keeps whatever reached it.  This is the in-process
        stand-in for kill -9; :meth:`shut` is the orderly exit."""
        ServerRole.shut(self)
        if self.journal is not None:
            self.journal.close()
            self.journal = None
        if self.persist is not None:
            self.persist.kill()
            if self.data_agent is not None:
                self.data_agent.pipeline = None
            self.persist = None

    def shut(self) -> None:
        # pending-save drain: stage every live session player BEFORE the
        # sockets come down, then give the flusher a bounded window to
        # empty the queue — anything still unflushed (store down) stays
        # durable in the WAL for the next pipeline over this directory
        if self.persist is not None and self.data_agent is not None:
            for sess in self.sessions.values():
                if (sess.guid is not None
                        and sess.guid in self.kernel.store.guid_map):
                    self.data_agent.save(sess.guid)
        super().shut()
        if self.journal is not None:
            self.journal.close()
            self.journal = None
        if self.persist is not None:
            self.persist.drain(self._persist_drain_timeout)
            self.persist.close()
            if self.data_agent is not None:
                self.data_agent.pipeline = None
            self.persist = None

    def _queue_change(self, cname: str, pname: str, rows: np.ndarray) -> None:
        """Property-event sink: accumulate changed rows per (class, prop);
        flushed once per frame (per-write callbacks → per-tick batch)."""
        key = (cname, pname)
        prev = self._changed.get(key)
        self._changed[key] = (
            rows.copy() if prev is None else np.union1d(prev, rows)
        )

    # ---------------------------------------------------- record accumulation
    def _rec_bucket(self, cname: str, rname: str) -> Dict[str, object]:
        b = self._rec_changed.get((cname, rname))
        if b is None:
            # resync: rows whose FINAL state should be re-sent wholesale
            # (used -> add-row, unused -> remove).  Swaps land here: a
            # fixed replay order can't preserve intra-frame interleaving
            # of swap with other ops, but final-state resync always can.
            b = {"add": set(), "del": set(), "upd": {}, "resync": set()}
            self._rec_changed[(cname, rname)] = b
        return b

    def _on_record_host(self, cname, rname, op, erows, rec_row, tags) -> None:
        """Host-path per-op record hook (store mutators)."""
        if (cname, rname) not in self._synced_records:
            return
        b = self._rec_bucket(cname, rname)
        if op == RecordOp.ADD:
            for e in erows:
                key = (int(e), int(rec_row))
                if key not in b["resync"]:
                    b["add"].add(key)
        elif op == RecordOp.DEL:
            for e in erows:
                key = (int(e), int(rec_row))
                b["del"].add(key)
                b["add"].discard(key)
                b["upd"].pop(key, None)
                b["resync"].discard(key)
        elif op == RecordOp.UPDATE:
            for e in erows:
                key = (int(e), int(rec_row))
                if key in b["add"] or key in b["resync"]:
                    continue  # full-row send already pending
                cur = b["upd"].get(key, set())
                if cur is None or tags is None:
                    b["upd"][key] = None  # None = resend every column
                else:
                    b["upd"][key] = cur | set(tags)
        elif op == RecordOp.SWAP:
            origin, target = rec_row
            for e in erows:
                for r in (int(origin), int(target)):
                    key = (int(e), r)
                    b["resync"].add(key)
                    b["add"].discard(key)
                    b["upd"].pop(key, None)
                    b["del"].discard(key)

    def _on_record_diff(self, cname: str, rname: str, codes: np.ndarray) -> None:
        """Device-path record diff sink (buff expiry, stat groups, any
        jitted phase that rewrites record arrays)."""
        b = self._rec_bucket(cname, rname)
        ent, rr = np.nonzero(codes)
        for e, r, c in zip(ent.tolist(), rr.tolist(), codes[ent, rr].tolist()):
            key = (e, r)
            if c == REC_ADDED:
                if key not in b["resync"]:
                    b["add"].add(key)
            elif c == REC_REMOVED:
                b["del"].add(key)
                b["add"].discard(key)
                b["upd"].pop(key, None)
                b["resync"].discard(key)
            elif c == REC_UPDATED and key not in b["add"] and key not in b["resync"]:
                b["upd"][key] = None

    # ---------------------------------------------------- record serialization
    def _obj_ident(self, raw: int) -> Ident:
        g = self.kernel.store.guid_of_handle(int(raw))
        return guid_ident(g) if g is not None else Ident()

    def _record_cells(self, rs, r_i32, r_f32, r_vec, ent: int, r: int, tags):
        """Per-kind cell messages for one record row, via the ONE shared
        record→wire mapping (persist.codec.record_row_cells) so snapshots
        and per-change sync can never diverge."""
        from ...persist.codec import record_row_cells

        return record_row_cells(
            self.kernel.store, rs,
            r_i32[ent] if r_i32 is not None else None,
            r_f32[ent] if r_f32 is not None else None,
            r_vec[ent] if r_vec is not None else None,
            r, tags,
        )

    def _flush_records(self, player_idx=None) -> None:
        """Mid-session record sync: accumulated per-op + device-diff record
        changes → ACK_ADD_ROW / ACK_REMOVE_ROW / ACK_RECORD_* messages
        (reference OnRecordEvent, NFCGameServerNet_ServerModule.cpp:75-81)."""
        rec_changed, self._rec_changed = self._rec_changed, {}
        k = self.kernel
        if player_idx is None and rec_changed:
            player_idx = self._build_player_index()
        for (cname, rname), b in rec_changed.items():
            public = self._synced_records.get((cname, rname), False)
            spec = k.store.spec(cname)
            rs = spec.records[rname]
            rstate = k.state.classes[cname].records[rname]
            used = np.asarray(rstate.used)
            r_i32 = np.asarray(rstate.i32) if rs.n_i32 else None
            r_f32 = np.asarray(rstate.f32) if rs.n_f32 else None
            r_vec = np.asarray(rstate.vec) if rs.n_vec else None
            host = k.store._hosts[cname]
            rname_b = rname.encode()
            per_entity: Dict[int, Dict[str, object]] = {}

            def ops_of(e: int) -> Dict[str, object]:
                o = per_entity.get(e)
                if o is None:
                    o = {"del": [], "add": [], "upd": {}}
                    per_entity[e] = o
                return o

            for e, r in b["del"]:
                ops_of(e)["del"].append(r)
            for e, r in b["add"]:
                ops_of(e)["add"].append(r)
            # resync rows (swaps): final state decides add-row vs remove
            for e, r in b["resync"]:
                if used[e, r]:
                    ops_of(e)["add"].append(r)
                else:
                    ops_of(e)["del"].append(r)
            for (e, r), tags in b["upd"].items():
                ops_of(e)["upd"][r] = tags

            ent_rows = np.asarray(sorted(per_entity), np.int64)
            ent_cells = (
                self._rows_cells(cname, ent_rows)
                if ent_rows.size else np.zeros((0, 2), np.int64)
            )
            cell_of = {
                int(r): ent_cells[i].tolist() for i, r in enumerate(ent_rows)
            }
            vis_map = None
            if (public and self.interest_radius is not None
                    and self._interest_ok(cname)):
                # public record diffs reach only observers in range (and
                # the owner), same scope as the property lanes
                vis_map = self._interest_targets(cname, ent_rows)
            for e, ops in per_entity.items():
                guid = host.row_guid[e] if e < len(host.row_guid) else None
                if guid is None:
                    continue  # died since the change was queued
                sc, gr = cell_of[e]
                if vis_map is not None:
                    targets = list(vis_map.get(e, []))
                    if cname == "Player" and guid not in targets:
                        targets.append(guid)
                else:
                    targets = self._targets_from_index(
                        player_idx, guid, sc, gr, public, cname
                    )
                if not targets:
                    continue
                pid = guid_ident(guid)
                forward = public and cname == "Player"

                def emit(msg_id, msg):
                    self._broadcast(targets, msg_id, msg)
                    if forward:
                        self._forward_world(msg_id, msg, pid)

                if ops["del"]:
                    emit(MsgID.ACK_REMOVE_ROW,
                         ObjectRecordRemove(
                             player_id=pid, record_name=rname_b,
                             remove_row=sorted(set(ops["del"]))))
                add_rows = []
                for r in sorted(set(ops["add"])):
                    if not used[e, r]:
                        continue  # added then removed within the frame
                    add_rows.append(record_row_struct(
                        k.store, rs,
                        r_i32[e] if r_i32 is not None else None,
                        r_f32[e] if r_f32 is not None else None,
                        r_vec[e] if r_vec is not None else None,
                        r))
                if add_rows:
                    emit(MsgID.ACK_ADD_ROW,
                         ObjectRecordAddRow(
                             player_id=pid, record_name=rname_b,
                             row_data=add_rows))
                u_ints: List[RecordInt] = []
                u_floats: List[RecordFloat] = []
                u_strings: List[RecordString] = []
                u_objects: List[RecordObject] = []
                u_vecs: List[RecordVector3] = []
                for r, tags in sorted(ops["upd"].items()):
                    if not used[e, r]:
                        continue
                    ints, floats, strings, objects, vecs = self._record_cells(
                        rs, r_i32, r_f32, r_vec, e, r, tags)
                    u_ints += ints
                    u_floats += floats
                    u_strings += strings
                    u_objects += objects
                    u_vecs += vecs
                if u_ints:
                    emit(MsgID.ACK_RECORD_INT,
                         ObjectRecordInt(player_id=pid, record_name=rname_b,
                                         property_list=u_ints))
                if u_floats:
                    emit(MsgID.ACK_RECORD_FLOAT,
                         ObjectRecordFloat(player_id=pid, record_name=rname_b,
                                           property_list=u_floats))
                if u_strings:
                    emit(MsgID.ACK_RECORD_STRING,
                         ObjectRecordString(player_id=pid, record_name=rname_b,
                                            property_list=u_strings))
                if u_objects:
                    emit(MsgID.ACK_RECORD_OBJECT,
                         ObjectRecordObject(player_id=pid, record_name=rname_b,
                                            property_list=u_objects))
                if u_vecs:
                    emit(MsgID.ACK_RECORD_VECTOR3,
                         ObjectRecordVector3(player_id=pid, record_name=rname_b,
                                             property_list=u_vecs))

    # ------------------------------------------- frame-batched target index
    def _build_player_index(self, player_class: str = "Player"):
        """One-frame broadcast index: players by (scene, group) and by
        scene — built with ONE device fetch per frame, replacing the
        per-entity broadcast_targets calls (each of which fetched whole
        columns; round-1: O(N) host cost at scale)."""
        k = self.kernel
        by_cell: Dict[Tuple[int, int], List[Guid]] = {}
        by_scene: Dict[int, List[Guid]] = {}
        spec = k.store.spec(player_class)
        cs = k.state.classes[player_class]
        host = k.store._hosts[player_class]
        rows = np.flatnonzero(host.alloc_mask)
        if rows.size:
            cols = gather_rows(
                cs.i32, rows,
                cols=[spec.slots["SceneID"].col, spec.slots["GroupID"].col],
            )
            for r, (sc, gr) in zip(rows.tolist(), cols.tolist()):
                g = host.row_guid[r]
                if g is None:
                    continue
                by_cell.setdefault((sc, gr), []).append(g)
                by_scene.setdefault(sc, []).append(g)
        return by_cell, by_scene

    def _targets_from_index(self, idx, guid: Guid, sc: int, gr: int,
                            public: bool, cname: str) -> List[Guid]:
        """GetBroadCastObject over the frame index: Public → players in the
        same (scene, group), GroupID 0 → scene-wide; Private → self if a
        player (NFCSceneAOIModule.cpp:531-593)."""
        if not public:
            return [guid] if cname == "Player" else []
        by_cell, by_scene = idx
        if gr == 0:
            return by_scene.get(sc, [])
        return by_cell.get((sc, gr), [])

    def _interest_targets(self, cname: str,
                          rows: np.ndarray) -> Dict[int, List[Guid]]:
        """Per-row visible OBSERVERS for the per-entity sync lanes: one
        device interest query over the changed rows, inverted into
        row -> [observer avatar guid].  With a radius set, "Public"
        means public to whoever can SEE you — not to the whole group
        (round-4 verdict item 4; reference broadcast scope is the
        coarse (scene, group), NFCSceneAOIModule.cpp:531-593)."""
        import jax.numpy as jnp

        out: Dict[int, List[Guid]] = {}
        if rows.size == 0:
            return out
        obs, obs_rows, obs_valid = self._observer_arrays()
        if not obs:
            return out
        k = self.kernel
        changed = np.zeros(k.store.capacity(cname), bool)
        changed[rows] = True
        cs = k.state.classes[cname]
        fn = self._interest_query(cname, len(obs_rows))
        if self._interest_skin > 0.0:
            ckey, cache = self._interest_cache_for(cname)
            vrows, vok, cache = fn(
                cs.vec, cs.i32, jnp.asarray(changed), cs.alive,
                k.state.classes["Player"].vec, k.state.classes["Player"].i32,
                jnp.asarray(obs_rows), jnp.asarray(obs_valid), cache,
            )
            self._interest_cache_store(ckey, cache)
        else:
            vrows, vok = fn(
                cs.vec, cs.i32, jnp.asarray(changed),
                k.state.classes["Player"].vec, k.state.classes["Player"].i32,
                jnp.asarray(obs_rows), jnp.asarray(obs_valid),
            )
        vrows, vok = np.asarray(vrows), np.asarray(vok)
        # nf-lint: disable=serve-loop -- per-entity property lane shared
        # by both engines; diffs here are < batch_sync_min rows, so the
        # loop is small-N (batching it is ROADMAP debt, not serve-path)
        for i, sess in enumerate(obs):
            g = sess.guid
            if g is None:
                continue
            for r in vrows[i][vok[i]].tolist():
                out.setdefault(int(r), []).append(g)
        return out

    def _rows_cells(self, cname: str, rows: np.ndarray) -> np.ndarray:
        """[n, 2] (SceneID, GroupID) for the given rows — one device
        gather instead of two get_property round trips per entity."""
        k = self.kernel
        spec = k.store.spec(cname)
        cs = k.state.classes[cname]
        return gather_rows(
            cs.i32, rows,
            cols=[spec.slots["SceneID"].col, spec.slots["GroupID"].col],
        )

    def _flush_changes(self) -> None:
        """The batched §3.3 spine: changed cells → grouped property-sync
        messages → proxy (client lists in the envelope).  All device reads
        are row-subset gathers done once per class per frame."""
        k = self.kernel
        sc = self.stage_clock
        with sc.stage("harvest"):
            changed, self._changed = self._changed, {}
            player_idx = self._build_player_index()
            obs_moved = False
            if self.interest_radius is not None:
                # observer-set gate: any session join/leave/respawn must
                # wake the interest lane even with zero Position diffs.
                # Lives in HARVEST (it walks the session dict — shared
                # bookkeeping, not serve work; the batched engine's
                # interest stage is loop-free, nf-lint serve-loop rule)
                obs_sig = tuple(sorted(
                    (key, s.guid)
                    for key, s in self.sessions.items()
                    if s.guid is not None
                    and s.guid in self.kernel.store.guid_map
                ))
                obs_moved = obs_sig != self._last_obs_sig
                self._last_obs_sig = obs_sig
                if self.serve_batch:
                    self._serve_refresh_table()
        # interest lane: Position diffs of synced classes leave as
        # per-session interest-filtered streams when a radius is set.
        # The pipeline only runs when something that can change a visible
        # set happened — a Position diff in the class, observer movement
        # (Player Position), an observer set change, or a create/destroy
        # in the class (the dirty marks) — so an idle world pays nothing.
        self._obs_cache = None  # one _observer_arrays() per flush
        if self.interest_radius is not None:
            with sc.stage("interest"):

                def zone_changed(cn: str) -> bool:
                    # visible sets mask on scene+group too — a swap with
                    # no Position diff still changes who sees whom.
                    # These keys are NOT popped: zone props also ride
                    # the normal broadcast sync.
                    return ((cn, "SceneID") in changed
                            or (cn, "GroupID") in changed)

                player_moved = ("Player", "Position") in changed \
                    or zone_changed("Player")
                for cname in self.sync_classes:
                    # only claim the diff when the class can ride the
                    # interest lane — non-spatial classes (no SceneID/
                    # GroupID) fall through to the broadcast lanes below
                    if not self._interest_ok(cname):
                        continue
                    pos_changed = changed.pop(
                        (cname, "Position"), None) is not None
                    if (pos_changed or player_moved or obs_moved
                            or zone_changed(cname)
                            or cname in self._interest_dirty):
                        self._interest_dirty.discard(cname)
                        if self.serve_overlap:
                            # double-buffered: serve this class's lane
                            # against the PRE-tick snapshot of the next
                            # frame, overlapping assembly with its tick
                            self._serve_pending[cname] = True
                        elif self.serve_batch:
                            self._send_interest_pos_batched(cname)
                        else:
                            self._send_interest_pos(cname)
        with sc.stage("encode"):
            # columnar fast lane: large public scalar/vector diffs leave
            # as packed-array batches (100k movers = a handful of
            # messages, not 100k python serializations)
            if self.batch_sync_min > 0:
                for key in [
                    kk for kk, rows in changed.items()
                    if rows.size >= self.batch_sync_min
                ]:
                    cname, pname = key
                    p = k.store.spec(cname).slot(pname).prop
                    if p.public and p.type in (
                        DataType.INT, DataType.FLOAT,
                        DataType.VECTOR2, DataType.VECTOR3,
                    ):
                        self._send_batch_property(
                            cname, pname, changed.pop(key), player_idx
                        )
            # regroup per (class, row): one message per entity per kind
            per_entity: Dict[Tuple[str, int], List[str]] = {}
            for (cname, pname), rows in changed.items():
                for row in rows:
                    per_entity.setdefault((cname, int(row)), []).append(pname)
            rows_by_class: Dict[str, np.ndarray] = {}
            for cname, row in per_entity:
                rows_by_class.setdefault(cname, []).append(row)
            pos_by_class: Dict[str, Dict[int, int]] = {}
            cells_by_class: Dict[str, np.ndarray] = {}
            vis_by_class: Dict[str, Dict[int, List[Guid]]] = {}
            for cname, rws in list(rows_by_class.items()):
                arr = np.asarray(sorted(set(rws)), np.int64)
                rows_by_class[cname] = arr
                pos_by_class[cname] = {int(r): i for i, r in enumerate(arr)}
                cells_by_class[cname] = self._rows_cells(cname, arr)
                if (self.interest_radius is not None
                        and self._interest_ok(cname)):
                    # device visibility query: interest work even though
                    # it feeds the encode loop below
                    with sc.stage("interest"):
                        vis_by_class[cname] = self._interest_targets(
                            cname, arr)
            sub_cache: Dict[Tuple[str, str], np.ndarray] = {}

            def bank_vals(cname: str, bank: Bank) -> np.ndarray:
                """Row-subset bank fetch, indexed by LOCAL position."""
                key = (cname, bank.value)
                if key not in sub_cache:
                    cs = k.state.classes[cname]
                    sub_cache[key] = gather_rows(
                        getattr(cs, bank.value), rows_by_class[cname]
                    )
                return sub_cache[key]

            for (cname, row), pnames in per_entity.items():
                host = k.store._hosts[cname]
                guid = host.row_guid[row] if row < len(host.row_guid) else None
                if guid is None:
                    continue  # died since the change was queued
                spec = k.store.spec(cname)
                pos = pos_by_class[cname][row]
                scn, gr = cells_by_class[cname][pos].tolist()
                # public props broadcast to the (scene, group); private-
                # only props go to the owner's client alone
                for public in (True, False):
                    sel = [
                        p for p in pnames
                        if bool(spec.slot(p).prop.public) is public
                        and (public or spec.slot(p).prop.private)
                    ]
                    if not sel:
                        continue
                    if public and cname in vis_by_class:
                        # interest lane: public to whoever can see you,
                        # plus always the owner's own client
                        targets = list(vis_by_class[cname].get(row, []))
                        if cname == "Player" and guid not in targets:
                            targets.append(guid)
                    else:
                        targets = self._targets_from_index(
                            player_idx, guid, scn, gr, public, cname
                        )
                    if not targets:
                        continue
                    self._send_property_msgs(
                        cname, pos, guid, sel, targets, bank_vals,
                        forward=(public and cname == "Player"),
                    )
            self._flush_records(player_idx)

    def _interest_build(self, cname: str):
        """Cached per-class jit of the interest lanes' device half that
        knows no session: quantize positions and bin rows into the cell
        table (ops/interest; the same stencil engine combat runs on).

        One program serves both lanes.  The Position lane bins ALL alive
        in-extent rows (`in_extent_only`), so the host can diff each
        session's visible set against what that session last saw:
        entities that moved while unobserved and then stopped are re-sent
        the moment an observer walks into range (the reference's
        enter-view resend, NFCSceneAOIModule OnObjectListEnter).  The
        property lanes bin the frame's changed rows as they are.  The
        sorts of a million rows are what the chip's compiler takes its
        time over (~30 s a program), and they are here, once a class and
        table size, not once a padded session count."""
        key = ("build", cname)
        fn = self._interest_jit.get(key)
        if fn is not None:
            return fn
        import jax.numpy as jnp

        from ...ops.interest import (
            _interest_feats,
            interest_table,
            quantize,
            table_seam,
        )
        from ...ops.verlet import refresh, sub_table

        spec = self.kernel.store.spec(cname)
        pos_col = spec.slots["Position"].col
        sc_col, gr_col = spec.slots["SceneID"].col, spec.slots["GroupID"].col
        extent = float(self.game_world.config.extent)
        skin = float(self._interest_skin)
        cell, width = self._interest_grid()
        bucket, spill_cells, spill_depth = self.resolved_interest(cname)

        if skin > 0.0:
            def interest_build(evec, ei32, alive, active, in_extent_only,
                               cache):
                pos3 = evec[:, pos_col]
                q, in_extent = quantize(pos3, alive, extent)
                binned = active & (in_extent | ~in_extent_only)
                # the cache anchors over the STABLE alive set; a frame's
                # rows ride a sub-table through its sorted order
                cache, _rebuilt = refresh(
                    cache, pos3, alive, cell, width, bucket, skin)
                feats = _interest_feats(
                    pos3, ei32[:, sc_col].astype(jnp.float32),
                    ei32[:, gr_col].astype(jnp.float32))
                table = sub_table(cache, binned & alive, feats,
                                  width * width, cell, width, bucket)
                return (q, *table_seam(table), cache)
        else:
            def interest_build(evec, ei32, alive, active, in_extent_only):
                pos3 = evec[:, pos_col]
                q, in_extent = quantize(pos3, alive, extent)
                table = interest_table(
                    pos3, active & (in_extent | ~in_extent_only),
                    ei32[:, sc_col].astype(jnp.float32),
                    ei32[:, gr_col].astype(jnp.float32),
                    cell, width, bucket, (spill_cells, spill_depth))
                return (q, *table_seam(table))

        fn = self.kernel.costbook.wrap(
            f"interest.build/{cname}", interest_build, stage="interest")
        self._interest_jit[key] = fn
        return fn

    def _interest_scan(self, cname: str, s_pad: int):
        """Cached per-(class, padded-session-count) jit of the other
        half: read each observer's 3x3 neighbourhood from a built table,
        both levels, distance+zone mask (ops/interest).  Only the
        payload and the level's index cross the seam; the geometry is
        static in both closures."""
        key = ("scan", cname, s_pad)
        fn = self._interest_jit.get(key)
        if fn is not None:
            return fn
        import jax.numpy as jnp

        from ...ops.interest import _scan_observers, seam_table

        pspec = self.kernel.store.spec("Player")
        p_pos = pspec.slots["Position"].col
        p_sc, p_gr = pspec.slots["SceneID"].col, pspec.slots["GroupID"].col
        radius = float(self.interest_radius)
        cell, width = self._interest_grid()
        bucket, spill_cells, spill_depth = self.resolved_interest(cname)

        def interest_scan(payload, hot_of, pvec, pi32, obs_rows, obs_valid):
            res = _scan_observers(
                seam_table(payload, hot_of, cell, width, bucket,
                           (spill_cells, spill_depth)),
                pvec[obs_rows, p_pos][:, :2],
                pi32[obs_rows, p_sc].astype(jnp.float32),
                pi32[obs_rows, p_gr].astype(jnp.float32),
                radius, cell,
            )
            ok = res.ok & obs_valid[:, None]
            # the widest visible set rides the frame's fetch
            return res.rows, ok, jnp.max(jnp.sum(ok, axis=1, dtype=jnp.int32))

        fn = self.kernel.costbook.wrap(
            f"interest.scan/{cname}", interest_scan, stage="interest")
        self._interest_jit[key] = fn
        return fn

    def _interest_lane(self, position: bool):
        """`_interest_build`'s `in_extent_only`, kept on the device."""
        flags = getattr(self, "_interest_lanes", None)
        if flags is None:
            import jax.numpy as jnp

            flags = self._interest_lanes = (jnp.asarray(False),
                                            jnp.asarray(True))
        return flags[bool(position)]

    def _interest_step(self, cname: str, s_pad: int):
        """The Position lane's interest pipeline for a padded session
        count: `_interest_build` over all alive in-extent rows, then
        `_interest_scan`.  Returns (q, rows, ok, counts[, cache]);
        `counts` = (the build's stats, the widest visible set), what
        `_observe_interest` reads."""
        build = self._interest_build(cname)
        scan = self._interest_scan(cname, s_pad)
        lane = self._interest_lane(True)

        if self._interest_skin > 0.0:
            def step(evec, ei32, alive, pvec, pi32, obs_rows, obs_valid,
                     cache):
                q, payload, hot_of, built, cache = build(
                    evec, ei32, alive, alive, lane, cache)
                rows, ok, widest = scan(
                    payload, hot_of, pvec, pi32, obs_rows, obs_valid)
                return q, rows, ok, (built, widest), cache
        else:
            def step(evec, ei32, alive, pvec, pi32, obs_rows, obs_valid):
                q, payload, hot_of, built = build(
                    evec, ei32, alive, alive, lane)
                rows, ok, widest = scan(
                    payload, hot_of, pvec, pi32, obs_rows, obs_valid)
                return q, rows, ok, (built, widest)
        return step

    def _interest_query(self, cname: str, s_pad: int):
        """The query-only interest pipeline: caller supplies the
        changed-row mask (any property's diff), gets per-observer visible
        candidates, through the Position lane's own two programs.  The
        changed rows of a cell are among its alive rows, so the sizes
        that hold the whole class hold any frame's diff."""
        build = self._interest_build(cname)
        scan = self._interest_scan(cname, s_pad)
        lane = self._interest_lane(False)

        if self._interest_skin > 0.0:
            def query(evec, ei32, changed, alive, pvec, pi32, obs_rows,
                      obs_valid, cache):
                _q, payload, hot_of, _built, cache = build(
                    evec, ei32, alive, changed, lane, cache)
                rows, ok, _widest = scan(
                    payload, hot_of, pvec, pi32, obs_rows, obs_valid)
                return rows, ok, cache
        else:
            def query(evec, ei32, changed, pvec, pi32, obs_rows, obs_valid):
                # (this lane binned changed rows alive or not: keep it)
                _q, payload, hot_of, _built = build(
                    evec, ei32, changed, changed, lane)
                rows, ok, _widest = scan(
                    payload, hot_of, pvec, pi32, obs_rows, obs_valid)
                return rows, ok
        return query

    def _interest_cache_for(self, cname: str):
        """The class's interest Verlet cache, carried in WorldState.aux
        (key "verlet/interest/<class>") so telemetry, invalidate() and
        sharded placement treat it like any other grid cache.  Registers
        the aux init lazily on first use."""
        from ...ops.verlet import init_cache

        k = self.kernel
        key = f"verlet/interest/{cname}"
        if key not in k._aux_init:
            cap = k.store.capacity(cname)
            k.register_aux(key, lambda c=cap: init_cache(c))
        k._ensure_aux()
        return key, k.state.aux[key]

    def _interest_cache_store(self, key: str, cache) -> None:
        k = self.kernel
        k.state = k.state.replace(aux={**k.state.aux, key: cache})

    def _interest_ok(self, cname: str) -> bool:
        """The interest lane needs spatial columns; classes without them
        stay on the broadcast lane."""
        slots = self.kernel.store.spec(cname).slots
        return all(n in slots for n in ("Position", "SceneID", "GroupID"))

    def _observer_arrays(self):
        """(sessions with live avatars, padded row array, validity mask);
        computed once per flush (_obs_cache cleared in _flush_changes)."""
        cached = getattr(self, "_obs_cache", None)
        if cached is not None:
            return cached
        from ...core.datatypes import next_pow2

        k = self.kernel
        # nf-lint: disable=serve-loop -- legacy engine's observer
        # collector (the parity oracle for NF_SERVE_BATCH); the batched
        # path reads the SessionTable columns instead
        obs = [
            s for s in self.sessions.values()
            if s.guid is not None and s.guid in k.store.guid_map
        ]
        if not obs:
            self._obs_cache = ([], None, None)
            return self._obs_cache
        rows = np.zeros(next_pow2(len(obs), lo=8), np.int32)
        for i, s in enumerate(obs):
            rows[i] = k.store.row_of(s.guid)[1]
        valid = np.zeros(rows.shape, bool)
        valid[: len(obs)] = True
        self._obs_cache = (obs, rows, valid)
        return self._obs_cache

    def _send_interest_pos(self, cname: str) -> None:
        """Per-session Position stream: ONE compact message per client
        carrying only the entities inside its interest radius, positions
        u16-quantized over the scene extent (scale rides the message).
        Replaces the group-broadcast lane for Position when
        `interest_radius` is set.

        Each session carries its OWN seen-state (sorted row array + guid +
        last-sent quantized position): an entity hits a session's wire
        when it enters that session's view (first sight or re-entry) or
        when its quantized position differs from what that session last
        received.  Leaving view drops the entity from the seen-state, so
        re-entry resends — the per-observer correctness the reference gets
        from OnObjectListEnter, without any global last-synced table (and
        hence no stale-row hazard when rows are recycled: the guid is part
        of the match)."""
        import jax
        import jax.numpy as jnp

        from ...ops.interest import QMAX
        from ..wire import InterestPosSync

        k = self.kernel
        spec = k.store.spec(cname)
        if "Position" not in spec.slots:
            return
        obs, obs_rows, obs_valid = self._observer_arrays()
        if not obs:
            return

        cs = k.state.classes[cname]
        pcs = k.state.classes["Player"]
        fn = self._interest_step(cname, len(obs_rows))
        if self._interest_skin > 0.0:
            ckey, cache = self._interest_cache_for(cname)
            q, rows, ok, stats, cache = fn(
                cs.vec, cs.i32, cs.alive,
                pcs.vec, pcs.i32,
                jnp.asarray(obs_rows), jnp.asarray(obs_valid), cache,
            )
            self._interest_cache_store(ckey, cache)
        else:
            q, rows, ok, stats = fn(
                cs.vec, cs.i32, cs.alive,
                pcs.vec, pcs.i32,
                jnp.asarray(obs_rows), jnp.asarray(obs_valid),
            )
        # the frame's one read: the answer, and beside it what the
        # table's build counted (the breach policy's input)
        q_np, rows_np, ok_np, (built, widest) = jax.device_get(
            (q, rows, ok, stats))
        q_np = q_np.astype(np.uint16)
        self._observe_interest(cname, list(built) + [widest])
        host = k.store._hosts[cname]
        scale = float(self.game_world.config.extent) / QMAX
        # nf-lint: disable=serve-loop -- the legacy per-session engine
        # itself (NF_SERVE_BATCH=0): kept as the bit-identity oracle for
        # tests/test_serve_batch.py, never the production hot path
        for i, sess in enumerate(obs):
            vis = rows_np[i][ok_np[i]]
            vis = vis[host.alloc_mask[vis]]  # drop just-died rows
            seen = getattr(sess, "_interest_seen", None)
            if seen is None:
                seen = self.reset_view(sess)
            vis = np.sort(vis)
            heads = host.guid_head[vis]
            datas = host.guid_data[vis]
            qv = q_np[vis]  # [n, 3]
            prev = seen.get(cname)
            if prev is None:
                send = np.ones(vis.size, bool)
                gone_h = gone_d = np.empty(0, np.int64)
            else:
                p_rows, p_heads, p_datas, p_q = prev
                idx = np.searchsorted(p_rows, vis)
                idx_c = np.minimum(idx, max(len(p_rows) - 1, 0))
                same = (
                    (len(p_rows) > 0)
                    & (p_rows[idx_c] == vis)
                    & (p_heads[idx_c] == heads)
                    & (p_datas[idx_c] == datas)
                    & np.all(p_q[idx_c] == qv, axis=-1)
                )
                send = ~same
                # leave-view: previously-seen guids whose row is gone from
                # the visible set (or recycled to another guid) — the
                # delta stream needs an explicit despawn signal
                if vis.size:
                    j = np.searchsorted(vis, p_rows)
                    j_c = np.minimum(j, vis.size - 1)
                    still = (
                        (vis[j_c] == p_rows)
                        & (heads[j_c] == p_heads)
                        & (datas[j_c] == p_datas)
                    )
                else:
                    still = np.zeros(len(p_rows), bool)
                gone_h, gone_d = p_heads[~still], p_datas[~still]
            if vis.size == 0:
                seen.pop(cname, None)
            else:
                seen[cname] = (vis, heads, datas, qv)
            if not send.any() and gone_h.size == 0:
                continue
            msg = InterestPosSync(
                scale=scale,
                count=int(send.sum()),
                svrid=heads[send].tobytes(),
                index=datas[send].tobytes(),
                qpos=np.ascontiguousarray(qv[send]).tobytes(),
                gone_svrid=gone_h.tobytes(),
                gone_index=gone_d.tobytes(),
            )
            self._send_to_session(sess, MsgID.ACK_INTEREST_POS, msg)

    # ------------------------------------------------ batched serve edge
    # ISSUE 13: the NF_SERVE_BATCH engine.  Same wire bytes as the legacy
    # loops above (tests/test_serve_batch.py proves bit-identity), but
    # the per-session set algebra runs as ONE vmap-over-sessions device
    # dispatch (ops/serving.py) against the SessionTable's seen-state,
    # and the host's only per-session work is slicing precomputed byte
    # buffers into packets (net/serving.py).

    def _interest_grid(self) -> Tuple[float, int]:
        """(cell size, width) of every class's interest grid.  A Verlet
        skin inflates the cell so the 3x3 read still covers the true
        radius from anchors up to skin/2 stale (ops/verlet.py)."""
        extent = float(self.game_world.config.extent)
        radius = float(self.interest_radius)
        skin = float(self._interest_skin)
        cell = radius + skin if skin > 0.0 else radius
        return cell, max(1, int(np.ceil(extent / cell)))

    def resolved_interest(self, cname: str) -> Tuple[int, int, int]:
        """(bucket, spill_cells, spill_depth) of the class's interest
        table in a program traced now, as `CombatModule.resolved_bucket`
        / `resolved_spill` state the neighbour engine's: a cell keeps
        `bucket` rows (`auto_bucket` of the capacity, doubled by every
        breach a deeper grid answered) and the first `spill_cells`
        over-full cells in cell order `spill_depth` more; (auto_bucket,
        0, 0) until a frame's drops breached the budget.  The Verlet
        path runs no second level."""
        from ...ops.stencil import auto_bucket

        _cell, width = self._interest_grid()
        cap = self.kernel.store.capacity(cname)
        bucket = min(auto_bucket(cap, width)
                     * self._interest_boost.get(cname, 1), max(cap, 1))
        if self._interest_skin > 0.0:
            return bucket, 0, 0
        cells, depth = self._interest_spill.get(cname, (0, 0))
        return bucket, min(cells, width * width), depth

    def _observe_interest(self, cname: str, stats) -> None:
        """Host side of a frame's interest build: publish what it
        counted (`stats`: ops/interest.STAT_NAMES, then the widest
        visible set) and hold its drops against the budget.  A breach is
        answered as combat's (`CombatModule._on_overflow`): a retrace
        with a larger table, announced, compiled by the next frame."""
        import logging

        from ...ops.interest import STAT_NAMES

        last = dict(zip(STAT_NAMES + ("candidates_max",),
                        (int(v) for v in stats)))
        self.interest_last[cname] = last
        self._interest_dropped.inc(last["dropped"], cls=cname)
        for name in ("hot_cells", "cell_rows_max", "spill_rows",
                     "candidates_max"):
            self._interest_gauges[name].set(last[name], cls=cname)
        _bucket, cells, depth = self.resolved_interest(cname)
        self._interest_gauges["spill_cells"].set(cells, cls=cname)
        self._interest_gauges["spill_depth"].set(depth, cls=cname)
        live = int(self.kernel.store.live_count(cname))
        dropped = last["dropped"]
        if live <= 0 or dropped <= self.interest_overflow_budget * live:
            return
        log = logging.getLogger("nf.interest")
        answer = self._answer_interest_breach(cname, last)
        if answer is not None:
            self._interest_resize(cname)
            log.warning(
                "interest table overflow (%s): %d/%d rows in no client's "
                "view (budget %.4f%%) — %s, interest programs retracing",
                cname, dropped, live, self.interest_overflow_budget * 100,
                answer)
        elif not self._interest_log_muted:
            self._interest_log_muted = True
            log.warning(
                "interest table overflow (%s): %d/%d rows in no client's "
                "view (budget %.4f%%) — resize exhausted; further "
                "breaches are counted (nf_interest_dropped_total) but "
                "not logged", cname, dropped, live,
                self.interest_overflow_budget * 100)

    def _answer_interest_breach(self, cname: str, seen: Dict[str, int]
                                ) -> Optional[str]:
        """What a budget breach changes, from what the breaching frame's
        build counted, or None when nothing is left to change: the rule
        of `CombatModule._answer_breach` (ops/stencil.py has it).  A few
        cells far over the bucket get the second level, sized with
        headroom and never smaller than it was; a class over-full
        everywhere, or over-full in so many cells that the level would
        be priced like the grid, gets its bucket doubled; with the
        doubling used up the level is what is left."""
        from ...ops import stencil

        bucket, cells, depth = self.resolved_interest(cname)
        _cell, width = self._interest_grid()
        boost = self._interest_boost.get(cname, 1)
        can_double = boost < self.interest_max_boost
        hot, most = seen["hot_cells"], seen["cell_rows_max"]
        if self._interest_skin <= 0.0:
            sized = stencil.second_level_size(hot, most, bucket)
            sized = (max(sized[0], cells), max(sized[1], depth))
            few = stencil.few_hot_cells(hot, most, bucket, width * width)
            if (stencil.deep_cell(most, bucket) and (few or not can_double)
                    and sized != (cells, depth)):
                self._interest_spill[cname] = sized
                return ("second level sized to %d hot cells, %d rows deep"
                        % sized)
        if can_double:
            self._interest_boost[cname] = boost * 2
            return "bucket boosted x%d" % (boost * 2)
        return None

    def _interest_resize(self, cname: str) -> None:
        """Make the class's interest programs retrace at the sizes
        `resolved_interest` now states.  Announced like a bucket boost
        of the tick (a CostBook generation bump), so the compiles that
        follow are the program's own and no hazard; the tick itself is
        not retraced.  Nobody is resent the world: the per-session
        engine's seen-state is by row and knows no width, the batched
        engine's `SeenTable` is widened in place (`seen_for`)."""
        with span("interest.resize", cls=cname):
            self.interest_resizes += 1
            self.kernel.costbook.generation_bump(f"interest_resize:{cname}")
            for jits in (self._interest_jit, self._serve_jit):
                for key in [key for key in jits if cname in key]:
                    del jits[key]
            getattr(self, "_serve_geom", {}).pop(cname, None)

    def _serve_geometry(self, cname: str):
        """(cell, width, bucket, m): grid geometry shared with the legacy
        jits — identical candidate sets are the parity precondition.  `m`
        is the seen-table width: 9 * (bucket + spill depth) covers every
        candidate slot of both levels exactly, so it follows the crowd
        with `resolved_interest`; NF_SERVE_SLOTS can cap it (memory at
        huge session counts) at the cost of dropping the farthest-slot
        candidates of overfull views for a frame."""
        geom = getattr(self, "_serve_geom", None)
        if geom is None:
            geom = self._serve_geom = {}
        g = geom.get(cname)
        if g is not None:
            return g
        cell, width = self._interest_grid()
        bucket, _cells, depth = self.resolved_interest(cname)
        m = 9 * (bucket + depth)
        cap_m = _env_int("NF_SERVE_SLOTS", 0)
        if cap_m > 0:
            m = min(m, cap_m)
        g = (cell, width, bucket, m)
        geom[cname] = g
        return g

    def _serve_refresh_table(self) -> None:
        """Harvest-stage SessionTable sync: one slot per session with a
        live avatar.  Slots of departed sessions free here (robust to
        every removal path), stale seen-state wipes on realloc."""
        st = self._session_table
        for key in list(st.slot_of):
            if key not in self.sessions:
                st.release(key)
        k = self.kernel
        for key, s in self.sessions.items():
            if s.guid is not None and s.guid in k.store.guid_map:
                st.ensure(key, s.conn_id, k.store.row_of(s.guid)[1])
            else:
                st.invalidate(key)

    def _serve_prepare(self, cname: str):
        """Per-class 'prepare' jit: quantize + position-version bump +
        cell-table build, ONCE per frame regardless of session chunking
        (per-chunk bumping would multi-count a single move)."""
        key = ("sprep", cname)
        fn = self._serve_jit.get(key)
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp

        from ...ops.interest import (
            _interest_feats,
            interest_table,
            quantize,
            table_seam,
        )
        from ...ops.serving import bump_qver
        from ...ops.verlet import refresh, sub_table

        spec = self.kernel.store.spec(cname)
        pos_col = spec.slots["Position"].col
        sc_col, gr_col = spec.slots["SceneID"].col, spec.slots["GroupID"].col
        extent = float(self.game_world.config.extent)
        skin = float(self._interest_skin)
        cell, width, bucket, _m = self._serve_geometry(cname)
        _bucket, spill_cells, spill_depth = self.resolved_interest(cname)

        if skin > 0.0:
            def prep(evec, ei32, alive, qver, prev_q, cache):
                pos3 = evec[:, pos_col]
                q, in_extent = quantize(pos3, alive, extent)
                qver2, prev2 = bump_qver(q, prev_q, qver)
                cache, _rebuilt = refresh(
                    cache, pos3, alive, cell, width, bucket, skin
                )
                feats = _interest_feats(
                    pos3,
                    ei32[:, sc_col].astype(jnp.float32),
                    ei32[:, gr_col].astype(jnp.float32),
                )
                table = sub_table(
                    cache, in_extent & alive, feats, width * width,
                    cell, width, bucket,
                )
                return (q, qver2, prev2, *table_seam(table), cache)
        else:
            def prep(evec, ei32, alive, qver, prev_q):
                pos3 = evec[:, pos_col]
                q, in_extent = quantize(pos3, alive, extent)
                qver2, prev2 = bump_qver(q, prev_q, qver)
                table = interest_table(
                    pos3, in_extent,
                    ei32[:, sc_col].astype(jnp.float32),
                    ei32[:, gr_col].astype(jnp.float32),
                    cell, width, bucket, (spill_cells, spill_depth),
                )
                return (q, qver2, prev2, *table_seam(table))

        fn = self.kernel.costbook.wrap(
            f"serve.prepare/{cname}", prep, stage="interest"
        )
        self._serve_jit[key] = fn
        return fn

    def _serve_scan(self, cname: str, s_chunk: int):
        """Per-(class, chunk) 'scan' jit: 3x3 candidate read + the full
        delta set algebra for a contiguous block of session slots.  Only
        the payload array crosses the prepare/scan seam — the grid
        geometry is static in both closures."""
        key = ("sscan", cname, s_chunk)
        fn = self._serve_jit.get(key)
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp

        from ...ops.interest import _scan_observers, seam_table
        from ...ops.serving import SeenTable, interest_delta, slot_compact

        pspec = self.kernel.store.spec("Player")
        p_pos = pspec.slots["Position"].col
        p_sc, p_gr = pspec.slots["SceneID"].col, pspec.slots["GroupID"].col
        radius = float(self.interest_radius)
        cell, width, bucket, m = self._serve_geometry(cname)
        _bucket, spill_cells, spill_depth = self.resolved_interest(cname)
        k9 = 9 * (bucket + spill_depth)

        def scan(payload, hot_of, pvec, pi32, obs_rows, valid, alloc_ok,
                 gen, qver, seen_rows, seen_gen, seen_qver):
            res = _scan_observers(
                seam_table(payload, hot_of, cell, width, bucket,
                           (spill_cells, spill_depth)),
                pvec[obs_rows, p_pos][:, :2],
                pi32[obs_rows, p_sc].astype(jnp.float32),
                pi32[obs_rows, p_gr].astype(jnp.float32),
                radius, cell,
            )
            # device-side alloc filter: the legacy loop's just-died-row
            # drop (host.alloc_mask), applied before the delta algebra
            ok = res.ok & valid[:, None] & alloc_ok[res.rows]
            rows = res.rows
            if m < k9:  # NF_SERVE_SLOTS cap: keep slot-order prefix
                rows, counts = slot_compact(rows, ok)
                rows = rows[:, :m]
                ok = jnp.arange(m, dtype=jnp.int32)[None, :] < counts[:, None]
            delta = interest_delta(
                rows, ok, gen, qver,
                SeenTable(seen_rows, seen_gen, seen_qver),
            )
            # the chunk's widest visible set rides its fetch
            return delta, jnp.max(jnp.sum(ok, axis=1, dtype=jnp.int32))

        fn = self.kernel.costbook.wrap(
            f"serve.scan/{cname}", scan, stage="interest"
        )
        self._serve_jit[key] = fn
        return fn

    def _serve_pos_collect(self, cname: str):
        """Device half of the batched Position lane: dispatch prepare +
        chunked scans and FETCH the dense delta buffers.  Returns the
        host-assembly payload, or None when there are no observers.
        Must run before tick dispatch (donation invalidates the serve
        kernel's input buffers); the returned dict needs no device."""
        import jax
        import jax.numpy as jnp

        k = self.kernel
        st = self._session_table
        if st.capacity == 0 or not st.valid.any():
            return None
        host = k.store._hosts[cname]
        cs = k.state.classes[cname]
        pcs = k.state.classes["Player"]
        _cell, _width, _bucket, m = self._serve_geometry(cname)

        cap = k.store.capacity(cname)
        qp = self._serve_qver.get(cname)
        if qp is None or qp[0].shape[0] != cap:
            # prev_q = -1: every row's first observed quantum counts as
            # a change, so a fresh engine never suppresses a first send
            qp = (jnp.zeros(cap, jnp.int32),
                  jnp.full((cap, 3), -1, jnp.int32))
        qver, prev_q = qp

        prep = self._serve_prepare(cname)
        if self._interest_skin > 0.0:
            ckey, cache = self._interest_cache_for(cname)
            q, qver, prev_q, payload, hot_of, built, cache = prep(
                cs.vec, cs.i32, cs.alive, qver, prev_q, cache
            )
            self._interest_cache_store(ckey, cache)
        else:
            q, qver, prev_q, payload, hot_of, built = prep(
                cs.vec, cs.i32, cs.alive, qver, prev_q
            )
        self._serve_qver[cname] = (qver, prev_q)

        gen = jnp.asarray(host.row_gen)
        alloc_ok = jnp.asarray(host.alloc_mask)
        obs_rows = jnp.asarray(st.avatar_row)
        valid = jnp.asarray(st.valid)
        seen = st.seen_for(cname, m)

        s_total = st.capacity
        chunk = _env_int("NF_SERVE_CHUNK", 0)
        if chunk <= 0 or chunk >= s_total:
            chunk = s_total
        parts = []
        for c0 in range(0, s_total, chunk):
            c1 = c0 + chunk
            fn = self._serve_scan(cname, chunk)
            delta, widest = fn(
                payload, hot_of, pcs.vec, pcs.i32,
                obs_rows[c0:c1], valid[c0:c1], alloc_ok, gen, qver,
                seen.rows[c0:c1], seen.gen[c0:c1], seen.qver[c0:c1],
            )
            self._serve_dispatches.inc()
            # the build's counts ride the chunk's own fetch
            parts.append(jax.device_get(
                (delta.vis, delta.send, delta.gone, delta.gone_rows,
                 widest, built)
            ))
            seen = type(seen)(
                rows=seen.rows.at[c0:c1].set(delta.seen.rows),
                gen=seen.gen.at[c0:c1].set(delta.seen.gen),
                qver=seen.qver.at[c0:c1].set(delta.seen.qver),
            )
        st.store_seen(cname, seen)
        self._serve_sessions_hist.observe(int(st.valid.sum()))
        self._observe_interest(
            cname, list(parts[0][5]) + [max(int(p[4]) for p in parts)])

        # gone lists carry guids AS LAST SERVED — freed rows have their
        # live guid zeroed, so gather from the previous run's mirrors
        prev_h, prev_d = self._serve_prev_guids.get(
            cname, (host.guid_head, host.guid_data)
        )
        self._serve_prev_guids[cname] = (
            host.guid_head.copy(), host.guid_data.copy()
        )
        cat = (lambda i: np.concatenate([p[i] for p in parts])
               if len(parts) > 1 else parts[0][i])
        return {
            "cname": cname,
            "q": np.asarray(q).astype(np.uint16),
            "vis": cat(0), "send": cat(1),
            "gone": cat(2), "gone_rows": cat(3),
            "prev_h": prev_h, "prev_d": prev_d,
        }

    def _serve_pos_emit(self, data) -> None:
        """Host half: batched frame assembly.  Flatten the [S, M] masks
        (row-major = session-major, per-session ascending because vis is
        sorted), gather ONCE from the host guid mirrors, materialize ONE
        payload per wire field, and slice per-session packets at cumsum
        byte offsets — zero per-session device syncs or numpy passes."""
        from ...ops.interest import QMAX
        from ..serving import segments
        from ..wire import InterestPosSync

        k = self.kernel
        st = self._session_table
        host = k.store._hosts[data["cname"]]
        q_np, vis, send = data["q"], data["vis"], data["send"]
        gone, gone_rows = data["gone"], data["gone_rows"]

        send_counts = send.sum(axis=1)
        gone_counts = gone.sum(axis=1)
        flat_rows = vis[send]
        heads_b = host.guid_head[flat_rows].tobytes()
        datas_b = host.guid_data[flat_rows].tobytes()
        qpos_b = np.ascontiguousarray(q_np[flat_rows]).tobytes()
        o8, _ = segments(send_counts, 8, heads_b)
        o6, _ = segments(send_counts, 6, qpos_b)
        flat_gone = gone_rows[gone]
        gh_b = data["prev_h"][flat_gone].tobytes()
        gd_b = data["prev_d"][flat_gone].tobytes()
        g8, _ = segments(gone_counts, 8, gh_b)

        scale = float(self.game_world.config.extent) / QMAX
        sent = 0
        for key, sess in self.sessions.items():
            slot = st.slot_of.get(key)
            if slot is None or not st.valid[slot]:
                continue
            ns, ng = int(send_counts[slot]), int(gone_counts[slot])
            if ns == 0 and ng == 0:
                continue
            msg = InterestPosSync(
                scale=scale,
                count=ns,
                svrid=heads_b[o8[slot]:o8[slot + 1]],
                index=datas_b[o8[slot]:o8[slot + 1]],
                qpos=qpos_b[o6[slot]:o6[slot + 1]],
                gone_svrid=gh_b[g8[slot]:g8[slot + 1]],
                gone_index=gd_b[g8[slot]:g8[slot + 1]],
            )
            self._send_to_session(sess, MsgID.ACK_INTEREST_POS, msg)
            sent += 1
        self._serve_packets.inc(sent)

    def _send_interest_pos_batched(self, cname: str) -> None:
        """Synchronous batched Position lane (NF_SERVE_BATCH without
        overlap): collect in the interest stage, assemble+send nested
        under 'assemble' so the waterfall attributes the host slicing."""
        data = self._serve_pos_collect(cname)
        if data is None:
            return
        with self.stage_clock.stage("assemble"):
            self._serve_pos_emit(data)

    def _serve_query(self, cname: str, s_pad: int):
        """Batched interest-scoped BatchPropertySync query jit: legacy
        `_interest_query` + device alloc filter + stable slot-order
        compaction (the lane's wire order is candidate slot order)."""
        key = ("bscan", cname, s_pad)
        fn = self._serve_jit.get(key)
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp

        from ...ops.interest import (
            visible_candidates,
            visible_candidates_cached,
        )
        from ...ops.serving import slot_compact

        k = self.kernel
        spec = k.store.spec(cname)
        pspec = k.store.spec("Player")
        pos_col = spec.slots["Position"].col
        sc_col, gr_col = spec.slots["SceneID"].col, spec.slots["GroupID"].col
        p_pos = pspec.slots["Position"].col
        p_sc, p_gr = pspec.slots["SceneID"].col, pspec.slots["GroupID"].col
        radius = float(self.interest_radius)
        skin = float(self._interest_skin)
        cell, width, bucket, _m = self._serve_geometry(cname)
        _bucket, spill_cells, spill_depth = self.resolved_interest(cname)

        if skin > 0.0:
            def query(evec, ei32, changed, alive, pvec, pi32, obs_rows,
                      valid, alloc_ok, cache):
                res, cache, _rebuilt = visible_candidates_cached(
                    cache, evec[:, pos_col], changed, alive,
                    ei32[:, sc_col].astype(jnp.float32),
                    ei32[:, gr_col].astype(jnp.float32),
                    pvec[obs_rows, p_pos][:, :2],
                    pi32[obs_rows, p_sc].astype(jnp.float32),
                    pi32[obs_rows, p_gr].astype(jnp.float32),
                    radius=radius, cell_size=cell, width=width,
                    bucket=bucket, skin=skin,
                )
                ok = res.ok & valid[:, None] & alloc_ok[res.rows]
                rows, counts = slot_compact(res.rows, ok)
                return rows, counts, cache
        else:
            def query(evec, ei32, changed, pvec, pi32, obs_rows, valid,
                      alloc_ok):
                res = visible_candidates(
                    evec[:, pos_col], changed,
                    ei32[:, sc_col].astype(jnp.float32),
                    ei32[:, gr_col].astype(jnp.float32),
                    pvec[obs_rows, p_pos][:, :2],
                    pi32[obs_rows, p_sc].astype(jnp.float32),
                    pi32[obs_rows, p_gr].astype(jnp.float32),
                    radius=radius, cell_size=cell, width=width,
                    bucket=bucket, spill=(spill_cells, spill_depth),
                )
                ok = res.ok & valid[:, None] & alloc_ok[res.rows]
                rows, counts = slot_compact(res.rows, ok)
                return rows, counts

        fn = self.kernel.costbook.wrap(
            f"serve.query/{cname}", query, stage="interest"
        )
        self._serve_jit[key] = fn
        return fn

    def _send_batch_property_interest_batched(
        self, cname: str, pname: str, rows: np.ndarray
    ) -> None:
        """Batched interest-scoped columnar sync: one device query for
        all sessions, one value gather, per-session byte slices."""
        import jax
        import jax.numpy as jnp

        from ..serving import segments
        from ..wire import BatchPropertySync

        k = self.kernel
        st = self._session_table
        host = k.store._hosts[cname]
        spec = k.store.spec(cname)
        slot = spec.slot(pname)
        rows = rows[host.alloc_mask[rows]]
        if rows.size == 0 or st.capacity == 0 or not st.valid.any():
            return
        cap = k.store.capacity(cname)
        changed = np.zeros(cap, bool)
        changed[rows] = True
        cs = k.state.classes[cname]
        pcs = k.state.classes["Player"]
        fn = self._serve_query(cname, st.capacity)
        obs_rows = jnp.asarray(st.avatar_row)
        valid = jnp.asarray(st.valid)
        alloc_ok = jnp.asarray(host.alloc_mask)
        if self._interest_skin > 0.0:
            ckey, cache = self._interest_cache_for(cname)
            vrows, counts, cache = fn(
                cs.vec, cs.i32, jnp.asarray(changed), cs.alive,
                pcs.vec, pcs.i32, obs_rows, valid, alloc_ok, cache,
            )
            self._interest_cache_store(ckey, cache)
        else:
            vrows, counts = fn(
                cs.vec, cs.i32, jnp.asarray(changed),
                pcs.vec, pcs.i32, obs_rows, valid, alloc_ok,
            )
        self._serve_dispatches.inc()
        vrows, counts = jax.device_get((vrows, counts))

        if slot.bank == Bank.VEC:
            vals = gather_rows(cs.vec, rows, cols=slot.col)[:, 0]
        elif slot.bank == Bank.F32:
            vals = gather_rows(cs.f32, rows, cols=slot.col)[:, 0]
        else:
            vals = gather_rows(cs.i32, rows, cols=slot.col)[:, 0]
        vals = np.asarray(vals)
        pos_of = np.full(cap, -1, np.int64)
        pos_of[rows] = np.arange(rows.size)

        with self.stage_clock.stage("assemble"):
            mask = np.arange(vrows.shape[1])[None, :] < counts[:, None]
            flat = vrows[mask]  # session-major, slot order per session
            heads_b = host.guid_head[flat].tobytes()
            datas_b = host.guid_data[flat].tobytes()
            vals_flat = np.ascontiguousarray(vals[pos_of[flat]])
            item = vals_flat.itemsize * (
                int(np.prod(vals_flat.shape[1:])) if vals_flat.ndim > 1
                else 1
            )
            data_b = vals_flat.tobytes()
            o8, _ = segments(counts, 8, heads_b)
            ov, _ = segments(counts, item, data_b)
            name_b, cls_b = pname.encode(), cname.encode()
            ptype = int(slot.prop.type)
            sent = 0
            for key, sess in self.sessions.items():
                si = st.slot_of.get(key)
                if si is None or not st.valid[si]:
                    continue
                n = int(counts[si])
                if n == 0:
                    continue
                msg = BatchPropertySync(
                    class_name=cls_b,
                    property_name=name_b,
                    ptype=ptype,
                    count=n,
                    svrid=heads_b[o8[si]:o8[si + 1]],
                    index=datas_b[o8[si]:o8[si + 1]],
                    data=data_b[ov[si]:ov[si + 1]],
                )
                self._send_to_session(sess, MsgID.ACK_BATCH_PROPERTY, msg)
                sent += 1
            self._serve_packets.inc(sent)

    def _send_batch_property_interest(self, cname: str, pname: str,
                                      rows: np.ndarray) -> None:
        """Interest-scoped columnar sync: each session gets ONE
        BatchPropertySync with only the changed entities inside its
        interest radius (same message type as the broadcast lane, so
        clients are agnostic to the fan-out mode)."""
        import jax.numpy as jnp

        from ..wire import BatchPropertySync

        k = self.kernel
        host = k.store._hosts[cname]
        spec = k.store.spec(cname)
        slot = spec.slot(pname)
        rows = rows[host.alloc_mask[rows]]
        if rows.size == 0:
            return
        obs, obs_rows, obs_valid = self._observer_arrays()
        if not obs:
            return
        cap = k.store.capacity(cname)
        changed = np.zeros(cap, bool)
        changed[rows] = True
        cs = k.state.classes[cname]
        fn = self._interest_query(cname, len(obs_rows))
        if self._interest_skin > 0.0:
            ckey, cache = self._interest_cache_for(cname)
            vrows, vok, cache = fn(
                cs.vec, cs.i32, jnp.asarray(changed), cs.alive,
                k.state.classes["Player"].vec, k.state.classes["Player"].i32,
                jnp.asarray(obs_rows), jnp.asarray(obs_valid), cache,
            )
            self._interest_cache_store(ckey, cache)
        else:
            vrows, vok = fn(
                cs.vec, cs.i32, jnp.asarray(changed),
                k.state.classes["Player"].vec, k.state.classes["Player"].i32,
                jnp.asarray(obs_rows), jnp.asarray(obs_valid),
            )
        vrows, vok = np.asarray(vrows), np.asarray(vok)
        # one value gather for the changed set; per-session subsets map
        # through pos_of (changed row -> position in `rows`)
        if slot.bank == Bank.VEC:
            vals = gather_rows(cs.vec, rows, cols=slot.col)[:, 0]
        elif slot.bank == Bank.F32:
            vals = gather_rows(cs.f32, rows, cols=slot.col)[:, 0]
        else:
            vals = gather_rows(cs.i32, rows, cols=slot.col)[:, 0]
        vals = np.asarray(vals)
        pos_of = np.full(cap, -1, np.int64)
        pos_of[rows] = np.arange(rows.size)
        name_b, cls_b = pname.encode(), cname.encode()
        ptype = int(slot.prop.type)
        # nf-lint: disable=serve-loop -- legacy columnar lane
        # (NF_SERVE_BATCH=0), the parity oracle for the batched
        # _send_batch_property_interest_batched above
        for i, sess in enumerate(obs):
            vis = vrows[i][vok[i]]
            vis = vis[host.alloc_mask[vis]]
            if vis.size == 0:
                continue
            idx = pos_of[vis]
            msg = BatchPropertySync(
                class_name=cls_b,
                property_name=name_b,
                ptype=ptype,
                count=int(vis.size),
                svrid=host.guid_head[vis].tobytes(),
                index=host.guid_data[vis].tobytes(),
                data=np.ascontiguousarray(vals[idx]).tobytes(),
            )
            self._send_to_session(sess, MsgID.ACK_BATCH_PROPERTY, msg)

    def _send_batch_property(self, cname: str, pname: str, rows: np.ndarray,
                             player_idx) -> None:
        """Columnar sync: ONE gather off the device + packed-array message
        per (scene, group) cell with observers.  This is the wire mirror
        of the SoA store — the per-entity proto path stays for strings,
        objects, private props and small diffs."""
        if self.interest_radius is not None and self._interest_ok(cname):
            if self.serve_batch:
                self._send_batch_property_interest_batched(cname, pname, rows)
            else:
                self._send_batch_property_interest(cname, pname, rows)
            return
        from ...kernel.scene import MAX_GROUPS_PER_SCENE
        from ..wire import BatchPropertySync

        k = self.kernel
        host = k.store._hosts[cname]
        spec = k.store.spec(cname)
        slot = spec.slot(pname)
        rows = rows[host.alloc_mask[rows]]  # drop rows that died
        if rows.size == 0:
            return
        cells = self._rows_cells(cname, rows)  # [n, 2]
        cs = k.state.classes[cname]
        if slot.bank == Bank.VEC:
            vals = gather_rows(cs.vec, rows, cols=slot.col)[:, 0]  # [n, 3]
        elif slot.bank == Bank.F32:
            vals = gather_rows(cs.f32, rows, cols=slot.col)[:, 0]
        else:
            vals = gather_rows(cs.i32, rows, cols=slot.col)[:, 0]
        heads = host.guid_head[rows]
        datas = host.guid_data[rows]
        cell_ids = cells[:, 0].astype(np.int64) * MAX_GROUPS_PER_SCENE + cells[:, 1]
        order = np.argsort(cell_ids, kind="stable")
        sorted_ids = cell_ids[order]
        uniq, starts = np.unique(sorted_ids, return_index=True)
        bounds = list(starts.tolist()) + [len(order)]
        name_b = pname.encode()
        cls_b = cname.encode()
        ptype = int(slot.prop.type)
        for i, cid in enumerate(uniq.tolist()):
            sc, gr = divmod(int(cid), MAX_GROUPS_PER_SCENE)
            targets = self._targets_from_index(
                player_idx, None, sc, gr, True, cname
            )
            if not targets:
                continue
            seg = order[bounds[i]:bounds[i + 1]]
            msg = BatchPropertySync(
                class_name=cls_b,
                property_name=name_b,
                ptype=ptype,
                count=int(seg.size),
                svrid=heads[seg].tobytes(),
                index=datas[seg].tobytes(),
                data=np.ascontiguousarray(vals[seg]).tobytes(),
            )
            self._broadcast(targets, MsgID.ACK_BATCH_PROPERTY, msg)

    def _forward_world(self, msg_id: int, msg: Message, pid: Ident) -> None:
        """Push a sync message up the world link for cross-game relay."""
        if self.cross_server_sync:
            self.world_link.send_to_all(int(msg_id), wrap(msg, player_id=pid))

    def _send_property_msgs(self, cname, row, guid, pnames, targets,
                            bank_vals, forward: bool = False) -> None:
        k = self.kernel
        spec = k.store.spec(cname)
        ints: List[PropertyInt] = []
        floats: List[PropertyFloat] = []
        strings: List[PropertyString] = []
        objects: List[PropertyObject] = []
        vec2s: List[PropertyVector2] = []
        vec3s: List[PropertyVector3] = []
        for pname in pnames:
            slot = spec.slot(pname)
            raw = bank_vals(cname, slot.bank)[row, slot.col]
            p = slot.prop
            if p.type == DataType.INT:
                ints.append(PropertyInt(
                    property_name=p.name.encode(), data=int(raw)))
            elif p.type == DataType.FLOAT:
                floats.append(PropertyFloat(
                    property_name=p.name.encode(), data=float(raw)))
            elif p.type == DataType.STRING:
                strings.append(PropertyString(
                    property_name=p.name.encode(),
                    data=k.store.strings.lookup(int(raw)).encode()))
            elif p.type == DataType.OBJECT:
                objects.append(PropertyObject(
                    property_name=p.name.encode(),
                    data=self._obj_ident(int(raw))))
            elif p.type == DataType.VECTOR2:
                vec2s.append(PropertyVector2(
                    property_name=p.name.encode(),
                    data=Vector2(x=float(raw[0]), y=float(raw[1]))))
            else:
                vec3s.append(PropertyVector3(
                    property_name=p.name.encode(),
                    data=Vector3(x=float(raw[0]), y=float(raw[1]),
                                 z=float(raw[2]))))
        pid = guid_ident(guid)
        # dedicated per-type messages matching the reference proto
        # (ObjectProperty{Int,Float,String,Object,Vector2,Vector3} all carry
        # player_id=1, property_list=2 — a protoc-generated client decodes
        # these directly)
        for msg_id, cls, items in (
            (MsgID.ACK_PROPERTY_INT, ObjectPropertyInt, ints),
            (MsgID.ACK_PROPERTY_FLOAT, ObjectPropertyFloat, floats),
            (MsgID.ACK_PROPERTY_STRING, ObjectPropertyString, strings),
            (MsgID.ACK_PROPERTY_OBJECT, ObjectPropertyObject, objects),
            (MsgID.ACK_PROPERTY_VECTOR2, ObjectPropertyVector2, vec2s),
            (MsgID.ACK_PROPERTY_VECTOR3, ObjectPropertyVector3, vec3s),
        ):
            if items:
                msg = cls(player_id=pid, property_list=items)
                self._broadcast(targets, msg_id, msg)
                if forward:
                    self._forward_world(msg_id, msg, pid)

    # --------------------------------------------------- cross-game delivery
    def _on_world_sync(self, _sid: int, msg_id: int, body: bytes) -> None:
        """World-relayed sync from another game server: deliver to every
        local client (world-scope visibility; the client mirror creates
        remote objects lazily on first property message)."""
        if not self.sessions:
            return
        base = MsgBase.decode(body)
        src = self._guid_of_ident(base.player_id)
        if src is not None and src in self.kernel.store.guid_map:
            return  # the entity lives here — local broadcast already covered it
        if msg_id == int(MsgID.ACK_ONLINE_NOTIFY):
            return  # mirror objects appear lazily with the first sync message
        per_conn: Dict[int, List[Ident]] = {}
        for sess in self.sessions.values():
            per_conn.setdefault(sess.conn_id, []).append(sess.ident)
        if msg_id == int(MsgID.ACK_OFFLINE_NOTIFY):
            leave = AckPlayerLeaveList(object_list=[base.player_id])
            for conn_id, idents in per_conn.items():
                self._send_to(idents, conn_id, MsgID.ACK_OBJECT_LEAVE, leave)
            return
        for conn_id, idents in per_conn.items():
            self.server.send_raw(
                conn_id, msg_id,
                MsgBase(player_id=base.player_id, msg_data=base.msg_data,
                        player_client_list=idents).encode(),
            )

    # ------------------------------------------------------------ leave events
    def _on_class_event(self, guid: Guid, _cname: str, ev: ObjectEvent) -> None:
        if ev == ObjectEvent.DESTROY and guid in self._guid_session:
            # destroyed outside _despawn (e.g. device death): clear binding
            key = self._guid_session.pop(guid)
            sess = self.sessions.get(key)
            if sess is not None:
                sess.guid = None
                self.reset_view(sess)

    def _on_npc_event(self, guid: Guid, _cname: str, ev: ObjectEvent) -> None:
        if ev == ObjectEvent.DESTROY and self.sessions:
            leave = AckPlayerLeaveList(object_list=[guid_ident(guid)])
            for sess in self.sessions.values():
                self._send_to_session(sess, MsgID.ACK_OBJECT_LEAVE, leave)
