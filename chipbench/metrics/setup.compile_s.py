"""setup.compile_s: seconds JAX spent building executables during set-up.

Summed from JAX's ``/jax/core/compile/backend_compile_duration``
monitoring events over the set-up call: compiles, and loads from the
persistent cache.  Absent where set-up built nothing.
"""

UNIT = "s"
HOOKS = {}


def read(ctx):
    setup = ctx["setup"]
    if not setup["executables"]:
        return None
    return setup["compile_s"]
