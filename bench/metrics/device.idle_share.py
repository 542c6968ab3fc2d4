"""Share of the time inside `bench.step` spans in which no operation ran
on the device, in %.  Waiting for arrivals does not count."""


def read(rec):
    tr = rec["trace"]
    if not tr or not tr["chips"] or tr["step_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_in_step_s"] / tr["step_s"])
