"""Median host wall time of the window's decode-only engine steps (each
ends in ``device_get``), in ms."""
import statistics


def read(data):
    ms = [1e3 * (s["t1"] - s["t0"]) for s in data.get("steps") or []
          if s["kind"] == "decode_steps"]
    return statistics.median(ms) if ms else None
