"""Share of decode slot-steps that served a request in the traced window:
decode_slot_steps / (decode_slot_steps + decode_idle_slot_steps), from the
engine's EngineStats deltas, in %."""


def read(rec):
    d = rec["stats_delta"]
    total = d["decode_slot_steps"] + d["decode_idle_slot_steps"]
    return 100.0 * d["decode_slot_steps"] / total if total else None
