"""Traffic generators, found by name.

A configuration's ``generator.kind`` and the one key of a traffic
mix's ``cut`` name the module ``<kind>_<cut>.py`` here, which
:func:`chipbench.gen.make_tracks` imports.  It defines

* ``make(gen, value, rng, target) -> Tracks`` -- the cut's tracks, from
  the configuration's ``generator`` table, the cut's value, the seeded
  ``numpy.random.Generator`` and the store's points per shard; and may
  define
* ``check_store(value, manifest)`` -- raise if the built store is not
  what the cut asks for.

Code shared by the cuts of one kind sits in ``<kind>.py``.  A new kind
or a new cut is a new module; nothing else changes.
"""
