"""What the compiler sized a program at, in GB (1e9 bytes) on one device: the
program's own record of its compiled step's ``memory_analysis()``
(``trace.registered_memory``, kept by ``Engine.compile()``). Prints the phase
line ``program_memory`` with all six fields in bytes. ``peak`` is XLA's
``peak_memory_in_bytes``: what decides whether the step fits. A program
without ``registered_memory`` (the parent of PR 37), or one that registered
nothing, reports nothing.

args: ``program`` ("train_step"), ``field`` ("peak", "temp", "argument",
"output", "alias", "generated_code").
"""

from chipbench import harness


def reduce(ctx, program, field="peak"):
    try:
        from shuffle_exchange_tpu.profiling import trace

        sizes = trace.registered_memory(program)
    except (ImportError, AttributeError):
        return None
    if not sizes:
        return None
    harness.emit(phase="program_memory", cell=ctx["cell"]["name"],
                 program=program, **sizes)
    value = sizes.get(field)
    return None if value is None else value / 1e9
