"""One module per kind of op loop, named by a traffic file's ``driver``:
each gives ``Load(config, traffic, seed, log)`` with ``setup``,
``window``, ``checks``, ``counters``, ``spans``, ``reseed`` and
``close`` (see `benchmark.generator`)."""
