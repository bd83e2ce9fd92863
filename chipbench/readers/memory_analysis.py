"""Gigabytes one device needs for ``program``, from the compiler's own
``memory_analysis()`` of it: arguments + outputs + temporaries - aliased.
The arguments are the resident state (weights, optimizer state, cache), so
this is the whole of what the program holds while it runs. Not
``memory_stats()['peak_bytes_in_use']``, which on this runtime reads the
resident arrays only."""


def read(context, program: str):
    found = context.get("programs", {}).get(program)
    return found["total"] / 1e9 if found else None
