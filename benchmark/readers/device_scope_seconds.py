"""Device self time, in seconds, of the traced span's operations by the scope
the program wrote around them with ``jax.named_scope`` (``trace_reduce.py``:
``scopes``, rows of scope path, pass, seconds; a path is the program's
components in order, ``seq.gdn/gdn.chunk``; an operation under none is
``(no scope)``).

``args["scopes"]``: components of which a path must hold one (left out: every
path, scoped or not); with ``args["first"]`` true the path must START with one
of them.  ``args["pass"]``: ``forward``, ``recompute`` (the forward a
``jax.checkpoint`` runs again) or ``backward`` (left out: all three).  The
time is whatever implements the scope: a kernel, the compiler's fusions, the
copies between them.  Nothing under the scope is nothing to read."""


def read(evidence: dict, args: dict):
    wanted, which = args.get("scopes"), args.get("pass")
    picked = []
    for path, run, seconds in (evidence.get("trace") or {}).get("scopes", []):
        parts = path.split("/")
        if which is not None and run != which:
            continue
        if wanted is None or set(parts[:1] if args.get("first") else parts) & set(wanted):
            picked.append(seconds)
    return sum(picked) if picked else None
