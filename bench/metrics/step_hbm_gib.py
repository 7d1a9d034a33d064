"""Device memory of the compiled step, argument + output + temporaries
- aliased bytes, by the compiler's ``memory_analysis``, in GiB."""


def read(run):
    m = run.memory
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes) / 2 ** 30
