"""compile_s: seconds of compilation (persistent-cache reads included)
during set-up, from the program's compile_stats()."""


def read(rec):
    return rec.compile.get("compile_s")
