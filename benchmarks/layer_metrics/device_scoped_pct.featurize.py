"""Device: share of the traced window's busy seconds whose operation resolves
to a scope of the program (a stage's name and what lies below it): what is
left ran under no name: instructions the compiler made (layout copies) and
other programs (`harness/scopes.py`)."""

from benchmarks.harness import scopes


def read(ctx):
    return scopes.scoped_pct(ctx)
