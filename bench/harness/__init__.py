"""The chip benchmark's harness: manifest, request builder, trace
reduction, plain references and the comparison that decides ``correct``.

Everything here is the yardstick.  From the program it takes only
``repro.core.run_sweep`` (the system under test) and what that returns.
"""
