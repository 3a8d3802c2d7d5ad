"""``compile_s``: seconds to trace, lower and compile the step
(``lower(...).compile()``); host clock. With the persistent compilation
cache warm this is the cache's load time."""


def read(run):
    return run.host.get("compile_s")
