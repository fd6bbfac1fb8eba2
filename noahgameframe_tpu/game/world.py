"""GameWorld: one-call assembly of the standard game stack.

The reference assembles a Game server from Plugin.xml: Kernel + Config +
GameServerPlugin (property/level/scene modules) + GameLogicPlugin
(skill/NPC modules) loaded into one NFCPluginManager
(_Out/Debug/Plugin.xml).  GameWorld is that composition as a library call,
plus the benchmark scenario builders used by bench.py and the BASELINE
configs.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import numpy as np

from ..core.schema import ClassRegistry
from ..core.store import StoreConfig
from ..kernel.component import ComponentModule
from ..kernel.kernel import Kernel
from ..kernel.plugin import Plugin, PluginManager
from ..kernel.scene import SceneModule
from .buff import BuffModule
from .combat import CombatModule, SkillModule
from .hero import HeroModule
from .items import EquipModule, ItemModule, PackModule
from .social import (
    FriendModule,
    GmModule,
    GuildModule,
    MailModule,
    PvpMatchModule,
    RankModule,
    ShopModule,
    TeamModule,
)
from .task import TaskModule
from .defines import COMM_PROPERTY_RECORD, PropertyGroup, STAT_NAMES
from .level import LevelModule
from .movement import MovementModule
from .scene_process import SCENE_TYPE_CLONE, SCENE_TYPE_NORMAL, SceneProcessModule  # noqa: F401
from .slg import SLGBuildingModule, SLGShopModule
from .property_config import PropertyConfigModule
from .regen import RegenModule
from .schema import standard_registry
from .stats import PropertyModule


@dataclasses.dataclass
class WorldConfig:
    npc_capacity: int = 1024
    player_capacity: int = 64
    extent: float = 512.0
    dt: float = 1.0 / 30.0
    seed: int = 0
    aoe_radius: float = 4.0
    aoi_bucket: Optional[int] = None  # None = auto-size from density
    respawn_s: float = 5.0
    attack_period_s: float = 1.0
    regen_period_s: float = 1.0
    combat: bool = True
    movement: bool = True
    regen: bool = True
    # Verlet skin for the combat grid (ops/verlet.py); None defers to the
    # NF_VERLET_SKIN env knob, <= 0 disables (rebuild every tick)
    verlet_skin: Optional[float] = None
    middleware: bool = True  # items/hero/task/buff stack
    # private is included so owner-only state (EXP, Gold, bag counters)
    # reaches its own client (GetBroadCastObject: Private -> self)
    diff_flags: tuple = ("public", "private", "upload")
    # config-selected spatial placement: a parallel.SpatialPlacement makes
    # GameWorld attach the full-row cross-shard migration phase (the
    # unified mesh engine); None keeps the world single-shard
    placement: Optional["object"] = None


def draw_npcs(rng: np.random.Generator, n: int, extent: float, camps: int):
    """What a seed decides about n spawned NPCs, in the one order the
    generator is consumed in: positions (z = 0), walk targets, camps."""
    pos = rng.uniform(0.0, extent, (n, 3)).astype(np.float32)
    pos[:, 2] = 0.0
    target = rng.uniform(0.0, extent, (n, 2)).astype(np.float32)
    return pos, target, rng.integers(0, camps, n)


def zipf_camp_sizes(n: int, camps: int, zipf: float) -> np.ndarray:
    """n NPCs shared out over `camps` spawn camps by a Zipf law: camp k
    (from 1) holds n k^-zipf / sum_j j^-zipf, the shares rounded down
    and the remainder handed to the largest fractions."""
    w = np.arange(1, camps + 1, dtype=np.float64) ** -float(zipf)
    share = n * w / w.sum()
    sizes = np.floor(share).astype(np.int64)
    short = int(n - sizes.sum())
    sizes[np.argsort(-(share - sizes), kind="stable")[:short]] += 1
    return sizes


def draw_about(rng: np.random.Generator, centres: np.ndarray,
               home: np.ndarray, leash: float, extent: float) -> np.ndarray:
    """One walk target a row: uniform on the leash's square about the
    row's camp, clipped to the extent (MovementModule's law)."""
    u = rng.random((home.size, 2), dtype=np.float32)
    return np.clip(centres[home] + np.float32(leash)
                   * (np.float32(2.0) * u - np.float32(1.0)),
                   np.float32(0.0), np.float32(extent)).astype(np.float32)


def draw_camp_npcs(rng: np.random.Generator, n: int, extent: float,
                   teams: int, camps: int, zipf: float, leash: float):
    """What a seed decides about n NPCs spawned on Zipf-sized camps, in
    the one order the generator is consumed in: the camps' centres
    (uniform over the extent less the leash on every side, so that a
    camp's square lies inside the world: a square cut by the border
    piles its clipped draws on the border line, and by both borders on
    the corner point), two walk targets a row about its camp, a
    point on the segment between them (where the walk would have the
    row; z = 0), teams.  Rows are handed out camp by camp, the largest
    first, as a scene's seed list is spawned.  Returns (pos, target,
    team, centres, home)."""
    margin = min(float(leash), extent / 2.0)
    centres = rng.uniform(margin, extent - margin,
                          (camps, 2)).astype(np.float32)
    home = np.repeat(np.arange(camps), zipf_camp_sizes(n, camps, zipf))
    start = draw_about(rng, centres, home, leash, extent)
    target = draw_about(rng, centres, home, leash, extent)
    along = rng.random((n, 1), dtype=np.float32)
    pos = np.zeros((n, 3), np.float32)
    pos[:, :2] = start + along * (target - start)
    return pos, target, rng.integers(0, teams, n), centres, home


class GameWorld:
    """The assembled standard stack; `.pm` is the plugin manager."""

    def __init__(self, config: Optional[WorldConfig] = None, registry: Optional[ClassRegistry] = None):
        self.config = cfg = config or WorldConfig()
        reg = registry or standard_registry()
        self.kernel = Kernel(
            reg,
            StoreConfig(
                default_capacity=64,
                capacities={
                    "NPC": cfg.npc_capacity,
                    "Player": cfg.player_capacity,
                    "IObject": 8,
                    "InitProperty": 8,
                    "Scene": 8,
                },
            ),
            dt=cfg.dt,
            seed=cfg.seed,
            diff_flags=cfg.diff_flags,
        )
        self.scene = SceneModule()
        self.scene_process = SceneProcessModule(self.scene)
        self.components = ComponentModule()
        self.property_config = PropertyConfigModule()
        self.properties = PropertyModule()
        self.level = LevelModule(self.property_config, self.properties)
        self.skills = SkillModule()
        modules = [self.kernel, self.scene, self.scene_process, self.components, self.property_config, self.properties, self.level, self.skills]
        self.pack = self.items = self.equip = self.heroes = self.tasks = None
        self.buffs = self.team = self.mail = self.rank = self.shop = None
        self.friends = self.guilds = self.gm = self.pvp = None
        self.slg_building = self.slg_shop = None
        if cfg.middleware:
            self.pack = PackModule()
            self.items = ItemModule(self.pack)
            self.equip = EquipModule(self.pack, self.properties)
            self.heroes = HeroModule(self.properties)
            self.heroes.scene_process = self.scene_process
            self.items.heroes = self.heroes
            self.items.level = self.level
            self.items.equip = self.equip
            self.equip.items = self.items
            self.tasks = TaskModule(self.level)
            self.buffs = BuffModule()
            self.team = TeamModule()
            self.mail = MailModule(self.pack)
            self.rank = RankModule()
            self.shop = ShopModule(self.pack)
            self.friends = FriendModule()
            self.guilds = GuildModule()
            self.gm = GmModule(self.level, self.pack)
            self.pvp = PvpMatchModule()
            self.slg_building = SLGBuildingModule(self.pack)
            self.slg_shop = SLGShopModule(self.pack, self.slg_building)
            modules += [self.pack, self.items, self.equip, self.heroes,
                        self.tasks, self.buffs, self.team, self.mail,
                        self.rank, self.shop, self.friends, self.guilds,
                        self.gm, self.pvp, self.slg_building, self.slg_shop]
        self.movement = None
        self.combat = None
        self.regen = None
        if cfg.movement:
            self.movement = MovementModule(extent=cfg.extent)
            modules.append(self.movement)
        if cfg.combat:
            self.combat = CombatModule(
                extent=cfg.extent,
                radius=cfg.aoe_radius,
                bucket=cfg.aoi_bucket,
                respawn_s=cfg.respawn_s,
                attack_period_s=cfg.attack_period_s,
                verlet_skin=cfg.verlet_skin,
            )
            modules.append(self.combat)
        if cfg.regen:
            self.regen = RegenModule(period_s=cfg.regen_period_s)
            modules.append(self.regen)
        self.migration = None
        if cfg.placement is not None:
            from ..parallel.rowmigrate import RowMigrationModule

            self.migration = RowMigrationModule(cfg.placement)
            modules.append(self.migration)
        # observability: registry + tracer + census, kernel-attached via
        # the pm lifecycle (after_init runs post kernel.build)
        from ..telemetry import TelemetryModule

        self.telemetry = TelemetryModule()
        modules.append(self.telemetry)
        if self.combat is not None:
            combat = self.combat
            self.telemetry.registry.register_callback(
                "nf_combat_fold_engine",
                lambda: -1 if combat.engine_baked is None
                else combat.engine_baked,
                kind="gauge",
                help="combat fold engine baked into the newest trace "
                     "(0 XLA, 1 Pallas fold; -1 before the first trace)",
            )
            npc_rows = cfg.npc_capacity
            self.telemetry.registry.register_callback(
                "nf_combat_spill_cells",
                lambda: combat.resolved_spill(npc_rows)[0], kind="gauge",
                help="over-full cells the neighbour engine's second level "
                     "holds (0 until a budget breach sized it)",
            )
            self.telemetry.registry.register_callback(
                "nf_combat_spill_depth",
                lambda: combat.resolved_spill(npc_rows)[1], kind="gauge",
                help="victims a hot cell keeps in the second level beyond "
                     "the base depth (0 until a budget breach sized it)",
            )

        # elastic mesh surface (parallel/elastic.py): populated by
        # .shard(); None keeps the world single-device
        self.sharded = None
        self.elastic = None

        self._rng = np.random.default_rng(cfg.seed)
        self.pm = PluginManager(app_name="game")
        self.pm.register_plugin(Plugin("KernelPlugin", [self.kernel]))
        self.pm.register_plugin(Plugin("ConfigPlugin", [self.property_config]))
        self.pm.register_plugin(
            Plugin("GameServerPlugin", [m for m in modules if m not in (self.kernel, self.property_config)])
        )

    def start(self) -> "GameWorld":
        self.pm.start()
        return self

    @property
    def all_modules(self):
        """Every registered module — the `modules` argument for
        persist.checkpoint save_world/load_world so host state (teams,
        guilds, mail, ranks, buff defs) survives a resume."""
        return list(self.pm.modules.values())

    def shard(self, n_devices: Optional[int] = None, mesh=None,
              ident_cols: Optional[Dict[str, int]] = None,
              exodus_tick_bound: int = 256, autoscaler=None):
        """Place the built world onto a device mesh and attach the
        elastic grow/drain driver.  With a config placement attached,
        the mesh defaults to the migration module's (they must agree —
        the migrate phase shard_maps over the same device set the state
        lives on); an explicit different width retargets the placement.
        Returns the :class:`~..parallel.elastic.ElasticMesh`."""
        import dataclasses as _dc

        from ..parallel.elastic import ElasticMesh
        from ..parallel.mesh import make_mesh
        from ..parallel.shard import ShardedKernel

        if mesh is None:
            if n_devices is None and self.migration is not None:
                mesh = self.migration.mesh
            else:
                mesh = make_mesh(n_devices)
        if self.migration is not None and mesh is not self.migration.mesh:
            self.migration.retarget(
                placement=_dc.replace(self.migration.placement,
                                      n_shards=int(mesh.devices.size)),
                mesh=mesh,
            )
        self.sharded = ShardedKernel(self.kernel, mesh=mesh)
        self.sharded.place()
        self.elastic = ElasticMesh(
            self.sharded, migration=self.migration,
            registry=self.telemetry.registry, ident_cols=ident_cols,
            exodus_tick_bound=exodus_tick_bound, autoscaler=autoscaler,
        )
        return self.elastic

    def save(self, path) -> None:
        from ..persist.checkpoint import save_world

        save_world(self.kernel, path, modules=self.all_modules)

    def load(self, path) -> None:
        from ..persist.checkpoint import load_world

        load_world(self.kernel, path, modules=self.all_modules)
        if self.sharded is not None:
            # cross-engine restore: the snapshot may come from a mesh of
            # any width (load_world leaves arrays uncommitted on the
            # default device) — drop every trace/cache and re-place the
            # restored state through world_shardings on the CURRENT mesh
            self.sharded.reshard(cause="snapshot_load")

    # -- seeding --------------------------------------------------------------

    def seed_npcs(
        self,
        n: int,
        scene: int = 1,
        group: int = 0,
        hp: int = 100,
        atk: int = 12,
        deff: int = 3,
        regen: int = 2,
        move_speed: int = 30000,
        camps: int = 2,
        rng: Optional[np.random.Generator] = None,
        spawn_camps: Optional[Dict[str, float]] = None,
    ) -> None:
        """Bulk-spawn n NPCs with randomized positions/camps — the NPC seed
        spawning of scene groups (NFCSceneAOIModule RequestEnterScene) at
        benchmark scale.  `spawn_camps` = {"camps", "zipf", "leash"}
        places them on Zipf-sized spawn camps (`draw_camp_npcs`) and
        leashes each walker to its own (MovementModule.set_homes);
        without it they are scattered over the extent, as ever."""
        # the world-owned generator advances across calls — two waves must
        # not land on identical coordinates
        if spawn_camps is None:
            pos, target, camp = draw_npcs(rng or self._rng, n,
                                          self.config.extent, camps)
        else:
            pos, target, camp, centres, home = draw_camp_npcs(
                rng or self._rng, n, self.config.extent, camps,
                int(spawn_camps["camps"]), float(spawn_camps["zipf"]),
                float(spawn_camps["leash"]))
        k = self.kernel
        values = {
            "SceneID": np.full(n, scene, np.int64).tolist(),
            "GroupID": np.full(n, group, np.int64).tolist(),
            "Position": [tuple(p) for p in pos],
            "TargetPos": [tuple(p) for p in target],
            "HP": [hp] * n,
            "Camp": camp.tolist(),
        }
        k.state, guids, rows = k.store.create_many(k.state, "NPC", n, values=values)
        # combat stats go through the EFFECTVALUE group of the stat record —
        # the recompute phase is the single source of truth for final stats
        # (reference NPCs likewise get theirs from the EffectData config,
        # NFCNPCRefreshModule.cpp:83-96)
        k.state = k.store.record_write_rows(
            k.state,
            "NPC",
            rows,
            COMM_PROPERTY_RECORD,
            int(PropertyGroup.EFFECTVALUE),
            {
                "MAXHP": [hp] * n,
                "HPREGEN": [regen] * n,
                "ATK_VALUE": [atk] * n,
                "DEF_VALUE": [deff] * n,
                "MOVE_SPEED": [move_speed] * n,
            },
        )
        if spawn_camps is not None and self.movement is not None:
            home_rows = np.zeros(k.store.capacity("NPC"), np.int32)
            home_rows[np.asarray(rows)] = home
            self.movement.set_homes(centres, home_rows,
                                    float(spawn_camps["leash"]))
        if self.combat is not None:
            self.combat.arm_all()
        if self.regen is not None:
            self.regen.arm_all("NPC")

    def tick(self):
        self.pm.run_once()

    def run(self, frames: int) -> None:
        self.pm.run(frames)


def build_benchmark_world(
    n_npcs: int,
    extent: Optional[float] = None,
    combat: bool = True,
    seed: int = 0,
    attack_period_s: float = 1.0,
    player_capacity: int = 64,
    movement: bool = True,
    placement=None,
    spawn_camps: Optional[Dict[str, float]] = None,
) -> GameWorld:
    """The staged BASELINE configs: density held at ~0.4 NPCs per world
    unit² so AOI cost scales with N, not with density.  `player_capacity`
    sizes the Player bank for served-path runs (bench.py --served seats
    one live avatar per simulated session).  `placement` (a
    parallel.SpatialPlacement over class "NPC") attaches the full-row
    migration phase for a world that is then `.shard()`ed over a mesh.
    `spawn_camps` = {"camps", "zipf", "leash"} stands the NPCs on
    Zipf-sized spawn camps at the same mean density (`seed_npcs`)."""
    if extent is None:
        extent = max(64.0, float(np.sqrt(n_npcs / 0.4)))
    cap = 1 << int(np.ceil(np.log2(max(n_npcs, 64))))
    w = GameWorld(
        WorldConfig(
            npc_capacity=cap,
            extent=extent,
            combat=combat,
            movement=movement,
            seed=seed,
            attack_period_s=attack_period_s,
            middleware=False,
            player_capacity=player_capacity,
            placement=placement,
        )
    )
    w.start()
    w.scene.create_scene(1, width=extent)
    w.seed_npcs(n_npcs, spawn_camps=spawn_camps)
    return w


class BenchmarkRoomRecipe:
    """`build_benchmark_world` as the recipe of a
    `parallel.rooms.RoomDirectory`.

    Called with a seed it builds that room's world, as any recipe does.
    `seeded_rows` is what lets the directory admit thousands of rooms
    without a world each: a seed decides a room's random key and its
    NPCs' positions, walk targets and camps and nothing else, so the
    leaves that hold those are made on the host for all the seeds at
    once, from the same draws in the same order, and every other leaf
    is the template room's."""

    CLASS = "NPC"
    CAMPS = 2  # seed_npcs' default

    def __init__(self, n_npcs: int, extent: float, **world):
        self.n_npcs = int(n_npcs)
        self.extent = float(extent)
        self.world = world

    def __call__(self, seed: int) -> GameWorld:
        return build_benchmark_world(self.n_npcs, extent=self.extent,
                                     seed=int(seed), **self.world)

    def seeded_rows(self, template: Kernel, seeds) -> Dict[str, np.ndarray]:
        """`[R, ...]` host leaves, by `ROOM_PACK_SPEC` path, of the
        rooms `self(seed)` would build, given the kernel of one."""
        seeds = [int(s) for s in seeds]
        spec = template.store.spec(self.CLASS)
        cs = template.state.classes[self.CLASS]
        rows = np.flatnonzero(np.asarray(cs.alive))
        if rows.size != self.n_npcs:
            raise ValueError(f"the template room holds {rows.size} NPCs, "
                             f"the recipe seeds {self.n_npcs}")
        i32 = np.repeat(np.asarray(cs.i32)[None], len(seeds), axis=0)
        vec = np.repeat(np.asarray(cs.vec)[None], len(seeds), axis=0)
        pos_col, target_col, camp_col = (
            spec.slot(n).col for n in ("Position", "TargetPos", "Camp"))
        for j, seed in enumerate(seeds):
            pos, target, camp = draw_npcs(np.random.default_rng(seed),
                                          self.n_npcs, self.extent,
                                          self.CAMPS)
            vec[j, rows, pos_col] = pos
            vec[j, rows, target_col, :2] = target
            i32[j, rows, camp_col] = camp
        # jax.random.PRNGKey(seed) with 32-bit types: (0, seed mod 2^32)
        rng = np.zeros((len(seeds), 2), np.uint32)
        rng[:, 1] = np.asarray(seeds, np.uint64) & np.uint64(0xFFFFFFFF)
        return {"rng": rng, f"classes.{self.CLASS}.i32": i32,
                f"classes.{self.CLASS}.vec": vec}
