"""Client wire handlers for the middleware surface: use-item, equip
wear/takeoff, tasks, teams, guilds — the receive-callback set the
reference's game server registers (NFCItemModule::OnClientUseItem,
NFCEquipModule, NFCTaskModule, NFCTeamModule, guild handlers)."""

from __future__ import annotations

import pytest

from noahgameframe_tpu.game import (
    GameWorld,
    ItemSubType,
    ItemType,
    PropertyGroup,
    TaskDef,
    TaskState,
    WorldConfig,
)
from noahgameframe_tpu.net.defines import MsgID
from noahgameframe_tpu.net.roles.base import RoleConfig
from noahgameframe_tpu.net.roles.game import GameRole, Session
from noahgameframe_tpu.net.transport import EV_MSG, NetEvent
from noahgameframe_tpu.net.wire import (
    AckSearchGuild,
    Ident,
    ItemStruct,
    MsgBase,
    ReqAcceptTask,
    ReqAckCreateGuild,
    ReqAckCreateTeam,
    ReqAckJoinGuild,
    ReqAckJoinTeam,
    ReqAckLeaveGuild,
    ReqAckLeaveTeam,
    ReqAckOprTeamMember,
    ReqAckUseItem,
    ReqCompeleteTask,
    ReqSearchGuild,
    ReqWearEquip,
    TakeOffEquip,
    ident_key,
    unwrap,
    wrap,
)


@pytest.fixture()
def rig():
    world = GameWorld(WorldConfig(combat=False, movement=False, regen=False,
                                  npc_capacity=64, player_capacity=8)).start()
    role = GameRole(
        RoleConfig(6, 0, "MidGame", "127.0.0.1", 0),
        backend="py", world=world, cross_server_sync=False,
    )
    sent = []
    role.server.send_raw = lambda c, m, b: (sent.append((c, m, b)), True)[1]

    def seat(i, account):
        ident = Ident(svrid=9, index=i)
        sess = Session(ident=ident, conn_id=100 + i, account=account)
        g = role.kernel.create_object(
            "Player", {"Name": account.title(), "Account": account},
            scene=1, group=0)
        sess.guid = g
        role.sessions[ident_key(ident)] = sess
        role._guid_session[g] = ident_key(ident)
        return ident, g

    def send(ident, msg_id, msg):
        conn = 100 + ident.index
        role.server.dispatch.feed([
            NetEvent(EV_MSG, conn, int(msg_id), wrap(msg, player_id=ident))
        ])

    def acks(conn, msg_id):
        return [b for c, m, b in sent
                if c == conn and m == int(msg_id)]

    return world, role, seat, send, acks


def test_use_item_and_equip_handlers(rig):
    world, role, seat, send, acks = rig
    e = world.kernel.elements
    e.add_element("Item", "hp_water", {"ItemType": int(ItemType.ITEM),
                                       "ItemSubType": int(ItemSubType.HP),
                                       "AwardValue": 30})
    e.add_element("Item", "axe", {"ItemType": int(ItemType.EQUIP),
                                  "ATK_VALUE": 6})
    ident, g = seat(1, "ann")
    k = world.kernel
    world.properties.set_group_value(g, "MAXHP", PropertyGroup.EFFECTVALUE,
                                     100)
    k.set_property(g, "HP", 10)
    world.pack.create_item(g, "hp_water", 1)
    send(ident, MsgID.REQ_ITEM_OBJECT,
         ReqAckUseItem(item=ItemStruct(item_id=b"hp_water", item_count=1)))
    assert int(k.get_property(g, "HP")) == 40
    assert acks(101, MsgID.ACK_ITEM_OBJECT)  # success echoed to the user

    # equip: use the token, wear via the wire, stats fold, then take off
    world.pack.create_item(g, "axe", 1)
    send(ident, MsgID.REQ_ITEM_OBJECT,
         ReqAckUseItem(item=ItemStruct(item_id=b"axe", item_count=1)))
    row = next(iter(world.pack.equips(g)))
    send(ident, MsgID.WEAR_EQUIP,
         ReqWearEquip(equipid=Ident(svrid=0, index=row)))
    assert world.properties.get_group_value(
        g, "ATK_VALUE", PropertyGroup.EQUIP) == 6
    send(ident, MsgID.TAKEOFF_EQUIP,
         TakeOffEquip(equipid=Ident(svrid=0, index=row)))
    assert world.properties.get_group_value(
        g, "ATK_VALUE", PropertyGroup.EQUIP) == 0


def test_task_handlers(rig):
    world, role, seat, send, acks = rig
    world.tasks.define_task(TaskDef("t1", target_config="", count=1,
                                    award_exp=0, award_gold=7))
    ident, g = seat(1, "bob")
    send(ident, MsgID.REQ_ACCEPT_TASK, ReqAcceptTask(task_id=b"t1"))
    assert world.tasks.status(g, "t1") == TaskState.IN_PROCESS
    world.tasks.add_process(g, "t1", 1)
    assert world.tasks.status(g, "t1") == TaskState.DONE
    gold0 = int(world.kernel.get_property(g, "Gold"))
    send(ident, MsgID.REQ_COMPLETE_TASK, ReqCompeleteTask(task_id=b"t1"))
    assert int(world.kernel.get_property(g, "Gold")) == gold0 + 7


def test_team_handlers_create_join_kick_leave(rig):
    world, role, seat, send, acks = rig
    cap_ident, cap = seat(1, "cap")
    mem_ident, mem = seat(2, "mem")
    send(cap_ident, MsgID.REQ_CREATE_TEAM, ReqAckCreateTeam())
    ack = acks(101, MsgID.ACK_CREATE_TEAM)
    assert ack
    _, created = unwrap(ack[-1], ReqAckCreateTeam)
    team_id = created.team_id

    send(mem_ident, MsgID.REQ_JOIN_TEAM, ReqAckJoinTeam(team_id=team_id))
    info = world.team.team_of(mem)
    assert info is not None and len(info.members) == 2
    joins = acks(102, MsgID.ACK_JOIN_TEAM)
    assert joins
    _, jmsg = unwrap(joins[-1], ReqAckJoinTeam)
    assert len(jmsg.xTeamInfo.teammemberInfo) == 2  # roster rides the ack

    # a non-captain cannot kick
    send(mem_ident, MsgID.REQ_OPRMEMBER_TEAM,
         ReqAckOprTeamMember(team_id=team_id,
                             member_id=Ident(svrid=cap.head,
                                             index=cap.data),
                             type=2))
    assert len(world.team.team_of(cap).members) == 2
    # the captain kicks the member
    send(cap_ident, MsgID.REQ_OPRMEMBER_TEAM,
         ReqAckOprTeamMember(team_id=team_id,
                             member_id=Ident(svrid=mem.head,
                                             index=mem.data),
                             type=2))
    assert world.team.team_of(mem) is None

    # leave dissolves the now-single-member team
    send(cap_ident, MsgID.REQ_LEAVE_TEAM, ReqAckLeaveTeam())
    assert world.team.team_of(cap) is None


def test_guild_handlers_create_join_search_leave(rig):
    world, role, seat, send, acks = rig
    lead_ident, lead = seat(1, "lead")
    mate_ident, mate = seat(2, "mate")
    send(lead_ident, MsgID.REQ_CREATE_GUILD,
         ReqAckCreateGuild(guild_name=b"Axiom"))
    assert acks(101, MsgID.ACK_CREATE_GUILD)
    assert world.guilds.find_by_name("Axiom") is not None

    send(mate_ident, MsgID.REQ_JOIN_GUILD,
         ReqAckJoinGuild(guild_name=b"Axiom"))
    assert len(world.guilds.find_by_name("Axiom").members) == 2
    assert acks(102, MsgID.ACK_JOIN_GUILD)

    send(mate_ident, MsgID.REQ_SEARCH_GUILD,
         ReqSearchGuild(guild_name=b"axi"))
    hits = acks(102, MsgID.ACK_SEARCH_GUILD)
    assert hits
    _, found = unwrap(hits[-1], AckSearchGuild)
    assert [x.guild_name for x in found.guild_list] == [b"Axiom"]
    assert found.guild_list[0].guild_member_count == 2

    send(mate_ident, MsgID.REQ_LEAVE_GUILD, ReqAckLeaveGuild())
    assert len(world.guilds.find_by_name("Axiom").members) == 1
    assert acks(102, MsgID.ACK_LEAVE_GUILD)


def test_sdk_guild_team_over_real_sockets():
    """SDK calls ride the full login pipeline to the middleware handlers
    (reference NFClient flow against the five-role cluster)."""
    from noahgameframe_tpu.client import GameClient
    from noahgameframe_tpu.net.roles import LocalCluster

    c = LocalCluster(http_port=0)
    c.start(timeout=25.0)
    try:
        cli = GameClient("mid")
        cli.connect("127.0.0.1", c.login.config.port)

        def pump(cond, t=12.0):
            assert c.pump_until(cond, extra=cli.execute, timeout=t), "timeout"

        pump(lambda: cli.connected)
        cli.login(); pump(lambda: cli.logged_in)
        cli.request_world_list(); pump(lambda: cli.worlds)
        cli.connect_world(cli.worlds[0].server_id)
        pump(lambda: cli.world_grant is not None)
        cli.connect_proxy(); pump(lambda: cli.connected)
        cli.verify_key(); pump(lambda: cli.key_verified)
        cli.select_server(c.game.config.server_id)
        pump(lambda: cli.server_selected)
        cli.create_role("Mid"); pump(lambda: cli.roles)
        cli.enter_game("Mid"); pump(lambda: cli.entered)

        cli.create_guild("Wire")
        pump(lambda: cli.guild_acks)
        cli.search_guild("wir")
        pump(lambda: cli.guild_search)
        assert [g.guild_name for g in cli.guild_search[-1].guild_list] \
            == [b"Wire"]

        cli.create_team()
        pump(lambda: cli.team_acks)
        assert cli.team_acks[-1].xTeamInfo is not None
    finally:
        c.shut()


def test_use_item_targets_row_zero(rig):
    """Row 0 is a VALID record row: a gem socketed into equip row 0 over
    the wire must not be coerced to 'untargeted' (review finding — the
    svrid==1 tag discriminates, since protoc clients always send the
    required targetid field zeroed)."""
    world, role, seat, send, acks = rig
    e = world.kernel.elements
    e.add_element("Item", "saber", {"ItemType": int(ItemType.EQUIP),
                                    "ATK_VALUE": 5})
    e.add_element("Item", "opal", {"ItemType": int(ItemType.GEM),
                                   "ATK_VALUE": 2})
    ident, g = seat(1, "zed")
    row = world.pack.create_equip(g, "saber")
    assert row == 0  # the first equip lands on record row 0
    world.pack.create_item(g, "opal", 1)
    send(ident, MsgID.REQ_ITEM_OBJECT,
         ReqAckUseItem(item=ItemStruct(item_id=b"opal", item_count=1),
                       targetid=Ident(svrid=1, index=0)))
    assert world.items.gems_of(g, 0) == ["opal"]
    # an explicitly ZEROED ident (what a protoc client sends when it has
    # no target) must stay untargeted — not become "equip row 0"
    world.pack.create_item(g, "opal", 1)
    send(ident, MsgID.REQ_ITEM_OBJECT,
         ReqAckUseItem(item=ItemStruct(item_id=b"opal", item_count=1),
                       targetid=Ident(svrid=0, index=0)))
    assert world.items.gems_of(g, 0) == ["opal"]  # unchanged (gem refused)
    assert world.pack.item_count(g, "opal") == 1  # stayed in the bag


def test_gm_command_wire(rig):
    """EGMI_REQ_CMD_NORMAL: typed GM commands gated by GMLevel."""
    from noahgameframe_tpu.net.wire import ReqCommand

    world, role, seat, send, acks = rig
    ident, g = seat(1, "gm")
    k = world.kernel
    # without GM level nothing happens
    send(ident, MsgID.REQ_CMD_NORMAL,
         ReqCommand(command_id=0, command_str_value=b"Level",
                    command_value_int=9))
    assert int(k.get_property(g, "Level")) != 9
    k.set_property(g, "GMLevel", 1)
    send(ident, MsgID.REQ_CMD_NORMAL,
         ReqCommand(command_id=0, command_str_value=b"Level",
                    command_value_int=9))
    assert int(k.get_property(g, "Level")) == 9
    # EGCT_MODIY_ITEM
    world.kernel.elements.add_element("Item", "gm_box", {"ItemType": 2})
    send(ident, MsgID.REQ_CMD_NORMAL,
         ReqCommand(command_id=1, command_str_value=b"gm_box",
                    command_value_int=3))
    assert world.pack.item_count(g, "gm_box") == 3


def test_pvp_match_and_ectype_wire(rig):
    """Apply → pair → room ack to both; ectype puts both fighters into
    ONE shared scene group."""
    from noahgameframe_tpu.net.wire import (
        AckPVPApplyMatch,
        ReqCreatePVPEctype,
        ReqPVPApplyMatch,
    )

    world, role, seat, send, acks = rig
    a_ident, a = seat(1, "reda")
    b_ident, b = seat(2, "blub")
    send(a_ident, MsgID.REQ_PVP_APPLY_MATCH,
         ReqPVPApplyMatch(nPVPMode=1, score=100))
    assert not acks(101, MsgID.ACK_PVP_APPLY_MATCH)  # alone: no match yet
    send(b_ident, MsgID.REQ_PVP_APPLY_MATCH,
         ReqPVPApplyMatch(nPVPMode=1, score=120))
    got_a = acks(101, MsgID.ACK_PVP_APPLY_MATCH)
    got_b = acks(102, MsgID.ACK_PVP_APPLY_MATCH)
    assert got_a and got_b  # both sides hear about the room
    _, ack = unwrap(got_a[-1], AckPVPApplyMatch)
    assert ack.nResult == 1 and ack.xRoomInfo is not None

    send(a_ident, MsgID.REQ_CREATE_PVP_ECTYPE,
         ReqCreatePVPEctype(xRoomInfo=ack.xRoomInfo))
    ect_a = acks(101, MsgID.ACK_CREATE_PVP_ECTYPE)
    ect_b = acks(102, MsgID.ACK_CREATE_PVP_ECTYPE)
    assert ect_a and ect_b
    k = world.kernel
    assert int(k.get_property(a, "GroupID")) == int(
        k.get_property(b, "GroupID"))  # one shared instance
    assert int(k.get_property(a, "GroupID")) > 1  # a fresh group
    # a second ectype request for the same room is refused (one-shot)
    n = len(acks(101, MsgID.ACK_CREATE_PVP_ECTYPE))
    send(a_ident, MsgID.REQ_CREATE_PVP_ECTYPE,
         ReqCreatePVPEctype(xRoomInfo=ack.xRoomInfo))
    assert len(acks(101, MsgID.ACK_CREATE_PVP_ECTYPE)) == n


def test_pvp_mode_segmentation_and_room_protection(rig):
    """Different PVP modes never pair (review finding), and a
    non-participant echoing a RoomID cannot destroy the pending room."""
    from noahgameframe_tpu.net.wire import (
        AckPVPApplyMatch,
        ReqCreatePVPEctype,
        ReqPVPApplyMatch,
    )

    world, role, seat, send, acks = rig
    a_ident, a = seat(1, "ma")
    b_ident, b = seat(2, "mb")
    x_ident, x = seat(3, "mx")
    send(a_ident, MsgID.REQ_PVP_APPLY_MATCH,
         ReqPVPApplyMatch(nPVPMode=1, score=100))
    send(b_ident, MsgID.REQ_PVP_APPLY_MATCH,
         ReqPVPApplyMatch(nPVPMode=2, score=100))
    assert not acks(101, MsgID.ACK_PVP_APPLY_MATCH)  # modes differ: no pair
    send(x_ident, MsgID.REQ_PVP_APPLY_MATCH,
         ReqPVPApplyMatch(nPVPMode=1, score=105))
    got = acks(101, MsgID.ACK_PVP_APPLY_MATCH)
    assert got  # same-mode pair a+x formed
    _, ack = unwrap(got[-1], AckPVPApplyMatch)

    # the mode-2 outsider echoes the room id: the room must survive
    send(b_ident, MsgID.REQ_CREATE_PVP_ECTYPE,
         ReqCreatePVPEctype(xRoomInfo=ack.xRoomInfo))
    assert not acks(102, MsgID.ACK_CREATE_PVP_ECTYPE)
    send(a_ident, MsgID.REQ_CREATE_PVP_ECTYPE,
         ReqCreatePVPEctype(xRoomInfo=ack.xRoomInfo))
    assert acks(101, MsgID.ACK_CREATE_PVP_ECTYPE)  # participants still can


def test_gm_modify_property_sets_named_property(rig):
    """EGCT_MODIY_PROPERTY SETS the named int property — not a gold add
    (review finding)."""
    from noahgameframe_tpu.net.wire import ReqCommand

    world, role, seat, send, acks = rig
    ident, g = seat(1, "gm2")
    k = world.kernel
    k.set_property(g, "GMLevel", 1)
    gold0 = int(k.get_property(g, "Gold"))
    send(ident, MsgID.REQ_CMD_NORMAL,
         ReqCommand(command_id=0, command_str_value=b"HP",
                    command_value_int=55))
    assert int(k.get_property(g, "HP")) == 55
    assert int(k.get_property(g, "Gold")) == gold0  # gold untouched
    # repeating is idempotent (set, not add)
    send(ident, MsgID.REQ_CMD_NORMAL,
         ReqCommand(command_id=0, command_str_value=b"HP",
                    command_value_int=55))
    assert int(k.get_property(g, "HP")) == 55


def test_pvp_room_mode_is_the_pairs_not_the_requesters(rig):
    """A pair formed by window-widening during ANOTHER mode's request
    must be labeled with the PAIR's queue mode (review finding), and an
    explicit score=0 must queue at 0, not fall back to Level."""
    from noahgameframe_tpu.net.wire import AckPVPApplyMatch, ReqPVPApplyMatch

    world, role, seat, send, acks = rig
    a_ident, a = seat(1, "wa")
    b_ident, b = seat(2, "wb")
    c_ident, c = seat(3, "wc")
    pvp = world.pvp
    pvp.window = 10
    send(a_ident, MsgID.REQ_PVP_APPLY_MATCH,
         ReqPVPApplyMatch(nPVPMode=2, score=100))
    send(b_ident, MsgID.REQ_PVP_APPLY_MATCH,
         ReqPVPApplyMatch(nPVPMode=2, score=150))
    assert not acks(101, MsgID.ACK_PVP_APPLY_MATCH)  # gap 50 > window 10
    # both tickets have been waiting; widening covers the gap now
    for t in pvp.queue:
        t.queued_at -= 10.0  # 10 s * widen_per_s 50 = +500 window
    send(c_ident, MsgID.REQ_PVP_APPLY_MATCH,
         ReqPVPApplyMatch(nPVPMode=1, score=100000))
    got_a = acks(101, MsgID.ACK_PVP_APPLY_MATCH)
    assert got_a  # a+b paired during c's request
    _, ack = unwrap(got_a[-1], AckPVPApplyMatch)
    assert ack.xRoomInfo.nPVPMode == 2  # the pair's mode, not c's 1
    # explicit zero rating queues at 0 (not Level)
    pvp.leave_queue(c)
    send(c_ident, MsgID.REQ_PVP_APPLY_MATCH,
         ReqPVPApplyMatch(nPVPMode=3, score=0))
    assert [t.score for t in pvp.queue if t.player == c] == [0]


def test_pvp_despawn_cleans_queue_and_rooms(rig):
    """Disconnect hygiene (review finding): a despawned player's ticket
    leaves the queue and their pending rooms are dropped."""
    from noahgameframe_tpu.net.wire import AckPVPApplyMatch, ReqPVPApplyMatch

    world, role, seat, send, acks = rig
    a_ident, a = seat(1, "da")
    b_ident, b = seat(2, "db")
    pvp = world.pvp
    send(a_ident, MsgID.REQ_PVP_APPLY_MATCH,
         ReqPVPApplyMatch(nPVPMode=1, score=100))
    assert any(t.player == a for t in pvp.queue)
    role._despawn(role.sessions[ident_key(a_ident)])
    assert not any(t.player == a for t in pvp.queue)  # ticket gone
    # matched room leaks: pair, then one side despawns before ectype
    a2_ident, a2 = seat(3, "da2")
    send(a2_ident, MsgID.REQ_PVP_APPLY_MATCH,
         ReqPVPApplyMatch(nPVPMode=1, score=100))
    send(b_ident, MsgID.REQ_PVP_APPLY_MATCH,
         ReqPVPApplyMatch(nPVPMode=1, score=110))
    assert role._pvp_rooms  # room pending
    role._despawn(role.sessions[ident_key(b_ident)])
    assert not role._pvp_rooms  # dropped with the fighter


def test_pvp_ectype_ack_self_id_is_per_recipient(rig):
    """Each fighter's ACK_CREATE_PVP_ECTYPE carries THEIR ident as
    self_id (review finding: both used to get the requester's)."""
    from noahgameframe_tpu.net.wire import (
        AckCreatePVPEctype,
        AckPVPApplyMatch,
        ReqCreatePVPEctype,
        ReqPVPApplyMatch,
    )

    world, role, seat, send, acks = rig
    a_ident, a = seat(1, "ea")
    b_ident, b = seat(2, "eb")
    send(a_ident, MsgID.REQ_PVP_APPLY_MATCH,
         ReqPVPApplyMatch(nPVPMode=1, score=100))
    send(b_ident, MsgID.REQ_PVP_APPLY_MATCH,
         ReqPVPApplyMatch(nPVPMode=1, score=100))
    _, ack = unwrap(acks(101, MsgID.ACK_PVP_APPLY_MATCH)[-1], AckPVPApplyMatch)
    send(a_ident, MsgID.REQ_CREATE_PVP_ECTYPE,
         ReqCreatePVPEctype(xRoomInfo=ack.xRoomInfo))
    from noahgameframe_tpu.net.roles.game import guid_ident

    for conn, g in ((101, a), (102, b)):
        _, e = unwrap(acks(conn, MsgID.ACK_CREATE_PVP_ECTYPE)[-1],
                      AckCreatePVPEctype)
        want = guid_ident(g)
        assert (e.self_id.svrid, e.self_id.index) == (want.svrid, want.index)


def test_pvp_survivor_notified_and_reapply_switches_mode(rig):
    """When a matched fighter despawns, the survivor hears nResult=0
    (review finding: silent stuck room); re-applying while queued
    switches the ticket to the new mode/score (review finding: silent
    drop)."""
    from noahgameframe_tpu.net.wire import AckPVPApplyMatch, ReqPVPApplyMatch

    world, role, seat, send, acks = rig
    a_ident, a = seat(1, "sa")
    b_ident, b = seat(2, "sb")
    send(a_ident, MsgID.REQ_PVP_APPLY_MATCH,
         ReqPVPApplyMatch(nPVPMode=1, score=100))
    send(b_ident, MsgID.REQ_PVP_APPLY_MATCH,
         ReqPVPApplyMatch(nPVPMode=1, score=110))
    assert role._pvp_rooms  # matched, room pending
    n_before = len(acks(101, MsgID.ACK_PVP_APPLY_MATCH))
    role._despawn(role.sessions[ident_key(b_ident)])
    got = acks(101, MsgID.ACK_PVP_APPLY_MATCH)
    assert len(got) == n_before + 1  # survivor notified
    _, cancel = unwrap(got[-1], AckPVPApplyMatch)
    assert cancel.nResult == 0  # cancelled, re-apply needed

    # re-apply switches: queue once in mode 1, again in mode 2
    send(a_ident, MsgID.REQ_PVP_APPLY_MATCH,
         ReqPVPApplyMatch(nPVPMode=1, score=50))
    send(a_ident, MsgID.REQ_PVP_APPLY_MATCH,
         ReqPVPApplyMatch(nPVPMode=2, score=70))
    tickets = [t for t in world.pvp.queue if t.player == a]
    assert [(t.mode, t.score) for t in tickets] == [(2, 70)]


def test_sdk_slg_gm_pvp_over_real_sockets():
    """The round-5 client surface end to end: GM commands, SLG city
    building, and PVP matchmaking ride the SDK through the five-role
    cluster to the game handlers and back (reference NFClient flow)."""
    from noahgameframe_tpu.client import GameClient
    from noahgameframe_tpu.game.defines import EShopType, ItemType
    from noahgameframe_tpu.net.roles import LocalCluster

    c = LocalCluster(http_port=0)
    c.start(timeout=25.0)
    try:
        gw = c.game.game_world
        e = gw.kernel.elements
        e.add_element("Building", "barracks", {"Type": 2})
        e.add_element("Shop", "shop_barracks", {
            "Type": int(EShopType.BUILDING), "Level": 3,
            "Gold": 100, "ItemID": "barracks"})
        e.add_element("Item", "gm_box", {"ItemType": int(ItemType.ITEM)})

        clis = []
        for name in ("reda", "blub"):
            cli = GameClient(name)
            cli.connect("127.0.0.1", c.login.config.port)

            def pump(cond, t=12.0, cli=cli):
                assert c.pump_until(cond, extra=cli.execute, timeout=t), \
                    "timeout"

            pump(lambda: cli.connected)
            cli.login(); pump(lambda: cli.logged_in)
            cli.request_world_list(); pump(lambda: cli.worlds)
            cli.connect_world(cli.worlds[0].server_id)
            pump(lambda: cli.world_grant is not None)
            cli.connect_proxy(); pump(lambda: cli.connected)
            cli.verify_key(); pump(lambda: cli.key_verified)
            cli.select_server(c.game.config.server_id)
            pump(lambda: cli.server_selected)
            cli.create_role(name.title()); pump(lambda: cli.roles)
            cli.enter_game(name.title()); pump(lambda: cli.entered)
            clis.append((cli, pump))
        (a, pump_a), (b, pump_b) = clis

        k = gw.kernel
        guids = {str(k.get_property(g, "Account")): g
                 for g in list(c.game._guid_session)}
        ga, gb = guids["reda"], guids["blub"]

        # GM: denied without GMLevel, then sets the named property
        a.gm_command(0, "Level", 5)
        k.set_property(ga, "GMLevel", 1)
        a.gm_command(0, "Level", 5)
        pump_a(lambda: int(k.get_property(ga, "Level")) == 5)
        # GM item grant reaches the bag
        a.gm_command(1, "gm_box", 2)
        pump_a(lambda: gw.pack.item_count(ga, "gm_box") == 2)

        # SLG: buy a building through the wire, then move it
        k.set_property(ga, "Gold", 500)
        a.slg_buy("shop_barracks", 10.0, 10.0)
        pump_a(lambda: a.slg_acks)
        rows = gw.slg_building.buildings(ga)
        assert rows, "building record row missing after buy"
        a.slg_move(next(iter(rows)), 14.0, 18.0)
        pump_a(lambda: len(a.slg_acks) >= 2)

        # PVP: both apply, both get the room, one mints the ectype
        # (pump BOTH clients: each client's socket drains in its own
        # execute(), so b's apply only leaves when b is pumped too)
        def pump_ab(cond, t=12.0):
            assert c.pump_until(
                cond, extra=lambda: (a.execute(), b.execute()), timeout=t
            ), "timeout"

        k.set_property(gb, "Level", 5)  # close scores pair immediately
        a.pvp_apply_match(mode=1)
        b.pvp_apply_match(mode=1)
        pump_ab(lambda: a.pvp_matches and b.pvp_matches)
        room_a = a.pvp_matches[-1].xRoomInfo
        assert room_a is not None and room_a.RoomID is not None
        a.pvp_create_ectype()
        pump_ab(lambda: a.pvp_ectypes)
    finally:
        c.shut()


def test_sdk_set_fight_hero_bytes_drive_the_server(rig):
    """GameClient.set_fight_hero's exact wire bytes (re-stamped with the
    proxy's player id, as the real proxy does) land the hero in the
    PlayerFightHero line-up."""
    from noahgameframe_tpu.client import GameClient
    from noahgameframe_tpu.game import ItemType

    world, role, seat, send, acks = rig
    e = world.kernel.elements
    e.add_element("Item", "hero_mage", {"ItemType": int(ItemType.CARD),
                                        "ATK_VALUE": 4})
    ident, g = seat(1, "ann")
    row = world.heroes.add_hero(g, "hero_mage")

    cli = GameClient("ann")
    captured = []

    class FakeConn:
        def send_msg(self, mid, body):
            captured.append((mid, body))
            return True

    cli._conn = FakeConn()
    cli.set_fight_hero(row, fight_pos=1)
    (mid, body), = captured
    assert mid == int(MsgID.REQ_SET_FIGHT_HERO)
    # the proxy stamps the player ident onto the envelope in flight
    base = MsgBase.decode(body)
    role.server.dispatch.feed([
        NetEvent(EV_MSG, 101, mid,
                 MsgBase(player_id=ident, msg_data=base.msg_data).encode())
    ])
    assert world.heroes.fight_hero(g, 1) == row


def test_login_burst_is_served_a_round_at_a_time(rig):
    """A burst of enter-game requests does not hold one execute() round
    for the whole burst: the game role dispatches client requests for at
    most a frame period per round (ServerRole.inbound_budget_seconds) and
    the rest waits, in arrival order.  With the budget at zero that is
    exactly one request per round."""
    from noahgameframe_tpu.net.wire import ReqEnterGameServer

    world, role, seat, send, acks = rig
    assert role.inbound_budget_seconds() == world.config.dt
    role.inbound_budget_seconds = lambda: 0.0
    burst = []
    for i in range(4):
        ident = Ident(svrid=9, index=i + 1)
        req = ReqEnterGameServer(id=ident, account=f"acc{i}".encode(),
                                 game_id=6, name=f"Hero{i}".encode())
        burst.append(NetEvent(EV_MSG, 100, int(MsgID.REQ_ENTER_GAME),
                              wrap(req, player_id=ident)))
    arrivals = [burst]
    role.server.transport.poll = lambda: arrivals.pop() if arrivals else []

    def entered():
        return [s.account for s in role.sessions.values()
                if s.guid is not None]

    for n in range(1, 5):
        role.execute()
        assert entered() == [f"acc{i}" for i in range(n)]
    assert role.pipeline_stats()["inbound_backlog_max"] == 3
    assert len(acks(100, MsgID.ACK_ENTER_GAME)) == 4
