"""Share of the loop's time inside handles that the EMBEDDER's own code
took: self time of the ``loop.embedder`` kind (steps of tasks whose
coroutine is not the program's, here the benchmark's generator, after
the program's spans opened inside them are taken out) over
``loop.steps_wall_s``."""

from chipbench.account import account


def read(run):
    acc = account(run)
    loop = (acc or {}).get("loop", {})
    if not loop.get("steps_wall_s"):
        return None
    own = acc["busy"].get(loop["thread"], {}).get("loop.embedder", {})
    return 100.0 * own.get("self_s", 0.0) / loop["steps_wall_s"]
