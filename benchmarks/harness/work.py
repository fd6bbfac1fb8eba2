"""The work a frame has to do, counted from the schema and not from the
arrays: what a roofline share divides into.

A frame of the NPC world has to read every property, record cell and
heartbeat of every live row once, and write every mutable one (the
properties and heartbeats; the frame never writes the record pages)
once.  The widths are the class schema's LOGICAL widths as the
configuration file states them under `row`, and the count is of live
rows: so a change of array layout, padding, dtype, bucket size or
neighbour engine changes the time and never the work.
"""

from __future__ import annotations

from typing import Any, Dict

INT_BYTES = 4
FLOAT_BYTES = 4
VEC3_BYTES = 12
TIMER_BYTES = 4 + 4 + 4 + 1  # next_fire, interval, remain, active
FLAG_BYTES = 1  # alive; a record row's `used`


def row_bytes(row: Dict[str, Any]) -> Dict[str, int]:
    """Bytes of one row by the schema: read once, written once."""
    props = (row["int_props"] * INT_BYTES + row["float_props"] * FLOAT_BYTES
             + row["vector_props"] * VEC3_BYTES)
    timers = row["heartbeats"] * TIMER_BYTES
    records = sum(rows * (cols * INT_BYTES + FLAG_BYTES)
                  for rows, cols in row["records"].values())
    return {"read": props + timers + records + FLAG_BYTES,
            "write": props + timers + FLAG_BYTES}


def tick_work(config: Dict[str, Any], live_rows: int) -> Dict[str, float]:
    """Bytes and operations one frame needs for `live_rows` entities.

    Operations: every attack whose heartbeat fired tests every entity
    within its radius (8 flops a pair: two subtractions, two products,
    a sum, a compare, a masked add, a max).  They are counted for the
    record; against a 197 TFLOP/s peak they never bound the frame."""
    rb = row_bytes(config["row"])
    world = config["world"]
    density = float(world["density_per_unit2"])
    radius = float(world["aoe_radius"])
    period_ticks = float(world["attack_period_s"]) / float(world["dt"])
    pairs = (live_rows / period_ticks) * density * 3.141592653589793 \
        * radius * radius
    return {"bytes": float(live_rows * (rb["read"] + rb["write"])),
            "flops": float(8.0 * pairs),
            "row_read_bytes": rb["read"], "row_write_bytes": rb["write"]}


def roofline_seconds(work: Dict[str, float], peaks: Dict[str, Any]):
    """(least seconds the chip could take, which of the two bounds it)."""
    by_bytes = work["bytes"] / peaks["bytes_per_s"]
    by_flops = work["flops"] / peaks["flops_per_s"]
    return (by_bytes, "bytes") if by_bytes >= by_flops \
        else (by_flops, "flops")
