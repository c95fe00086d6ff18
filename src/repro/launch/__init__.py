"""Launchers: the clustering CLI (``cluster``), JAX's persistent compile
cache for the entry points (``compile_cache``) and device-mesh
construction (``mesh``)."""
