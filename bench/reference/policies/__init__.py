"""Reference policies, one family per module; ``engine.policy`` finds a
family's module by its name (``-`` becomes ``_``)."""
