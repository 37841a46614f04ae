"""The serving runtime (sessions, scheduler, batch composite, faults).

Importing the package loads nothing else: the language-model step
builders live in ``repro.runtime.steps`` and are imported from there.
"""
