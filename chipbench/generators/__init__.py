"""Graph generators, one module per family, found by the ``generator``
key of a configuration file.  Each module exposes

- ``generate(cfg, seed)`` -> ``(chipbench.graph.HostCSR, extras)``: the
  graph, made on the device from the seed, and what the sampler needs;
- ``sample_edges(cfg, extras, rng, count)`` -> ``(src, dst)``: new edges
  from the family's own distribution, for the traffic's inserts.
"""
