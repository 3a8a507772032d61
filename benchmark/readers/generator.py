"""One number of the load generator's own record of the window
(``args["key"]`` of ``kinds/serve_open_loop.summarize``)."""


def read(evidence: dict, args: dict):
    return (evidence.get("generator") or {}).get(args["key"])
