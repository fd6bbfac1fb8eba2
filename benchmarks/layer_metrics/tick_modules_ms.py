"""tick_modules_ms: the host modules' execute(), per served frame.

Source: program span.  Summed time of the `nf.tick.modules` spans
(`PluginManager.execute_modules`: every module's `execute()`, then the
kernel's) inside the traced window / frames begun in it."""

from benchmarks.harness import hostspans


def read(run, trace):
    return hostspans.per_unit_ms(run, ("nf.tick.modules",), "frames")
