"""Loop-thread microseconds of the program's request path per submitted
request: self time of ``front.submit`` (router and pool up to the first
await), ``request.pack`` (the envelope's verify item) and ``req.admit``
(the resumed submitter, from the verdict until the pool has the request)
on the loop thread, over the ``front.submit`` calls.  Read only from an
account whose loop was hooked, so that the parent reports nothing."""

from chipbench.account import account

KINDS = ("front.submit", "request.pack", "req.admit")


def read(run):
    acc = account(run)
    if not acc or not acc.get("loop_steps", {}).get("covered"):
        return None
    loop = acc["busy"].get(acc["loop"]["thread"], {})
    calls = loop.get("front.submit", {}).get("calls")
    if not calls:
        return None
    return 1e6 * sum(loop.get(k, {}).get("self_s", 0.0)
                     for k in KINDS) / calls
