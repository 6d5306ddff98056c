"""Fixture package: phase emission sites for the phase-name drift rule.

``warp`` and ``drift`` are documented in docs/observability.md (clean);
``mystery_phase`` and ``renamed_mystery`` are not (each fires
``phase-undocumented:<name>``).
"""

import contextlib


class Sim:
    @contextlib.contextmanager
    def _phase(self, name, value=None):
        yield self

    def step(self):
        with self._phase("warp"):
            pass
        with self._phase("mystery_phase", "7") as ph:
            ph.name = "renamed_mystery"
        with self._phase("drift") as ph:
            # a name set on anything but the handle is not a phase
            self.name = "not_a_phase"
        # a name that is not a constant is not seen by the rule
        name = "dynamic"
        with self._phase(name):
            pass
