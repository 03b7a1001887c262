"""Compile: executables built or read back during set-up (every backend
compile event, the small helper programs included). A count."""


def read(ctx):
    return ctx["programs_built"]
