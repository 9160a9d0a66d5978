"""The benchmark's plain reference: a frozen copy of the scalar engine.

``core/`` (less the SoA backend), ``obs/metrics.py`` and
``scenarios/{modes,script}.py`` are copies of the system's event-driven
engine, its GHA compiler, policies and scenario DSL as they stood when
the benchmark was defined; :mod:`refsim.lockstep` drives them as the
system's lockstep seed fan does.  The reference imports nothing of the
system under test, so a change to the system cannot move it.
"""
