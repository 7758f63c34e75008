"""Model step, short pool: median wall milliseconds of the short
pool's engine steps that fused a prefill chunk with decode rows. Such
a step ends in the engine's token sync, so its span covers the device
work. (A prefill-only dispatch returns before the device finishes and
is not counted.)"""
import statistics


def read(rec):
    ms = [(s["t1"] - s["t0"]) * 1e3 for s in rec.spans
          if s["name"] == "step.short" and s.get("kind") == "mixed"]
    return statistics.median(ms) if ms else None
