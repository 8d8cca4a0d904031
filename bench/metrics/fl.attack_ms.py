"""Device time per round of the operations under the name scope
``update_attack`` (the update attacker of ``core/attacks.py``, called and
observed in ``fedfits.make_round``).  Nothing is read where no operation
carries the scope."""


def read(inp):
    t = inp.trace.scope_time("update_attack")
    if t <= 0 or not inp.window["rounds"]:
        return None
    return 1e3 * t / inp.window["rounds"]
