"""Stage program: on the chip that was busiest in the trace, its
device-busy milliseconds per stage call that ended inside the traced
window.  ``stage_ms`` minus this is the host's share of a call."""


def read(run):
    t = run.trace
    if t is None or not t.busy_by_chip:
        return None
    chip = max(t.busy_by_chip, key=t.busy_by_chip.__getitem__)
    stages = [s for s, c in enumerate(run.stage_chips)
              if run.chips[c].id == chip]
    calls = sum(len(t.stage_calls.get(s, [])) for s in stages)
    return t.busy_by_chip[chip] / calls * 1e3 if calls else None
