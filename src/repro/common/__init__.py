"""Shared infrastructure used across the battery system.

``repro.common.compat`` (the scoped x64 switch) and
``repro.common.runtime`` (compile cache, CPU device count) are live; the
growth seed's LM model-config layer lives in ``repro.common.config`` and is
imported directly by its remaining consumers rather than re-exported
here — an eager re-export would drag the quarantined LM stack into the
battery import graph (see DESIGN.md §9 on the RPA501 reachability rule).
"""
