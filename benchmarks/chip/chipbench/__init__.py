"""The chip benchmark's yardstick: everything that turns a cell named in
``BENCHMARK.json`` into numbers, kept apart from the program it measures.

From the program the benchmark takes only the system under test
(``repro.models.factory.build_model``, ``repro.serving.engine``), its
counters (``EngineStats``) and its kernel names in the device trace. Traffic
generation, weights, the float32 reference, the FLOP and byte counts, the
table of peaks and the reduction of traces to metrics all live here.
"""
