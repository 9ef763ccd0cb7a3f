"""One module per kind of system under test, chosen by the ``system``
key of a configuration file: ``systems/<system>.py`` defines
``run(cell, args, env) -> dict``."""
