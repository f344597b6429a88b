"""Extra-plugin loader.

Reference surface (SURVEY §2.1): RapidsPluginUtils.loadExtraPlugins
instantiates user-supplied plugin classes. (The reference's per-version
ShimLoader has no counterpart here: the engine is written for the one
installed jax and calls its public API directly.)

``load_extra_plugins`` applies srt.plugins ("pkg.module:attr" entries,
comma-separated): each attr is called with the active conf at
initialize time — the loadExtraPlugins contract for user extensions.
"""

from __future__ import annotations

from typing import List


def load_extra_plugins(conf) -> List[object]:
    """srt.plugins = 'pkg.module:attr,pkg2.mod:attr2' — import each and
    call attr(conf); returns the loaded plugin objects
    (RapidsPluginUtils.loadExtraPlugins role)."""
    import importlib

    from ..conf import EXTRA_PLUGINS
    spec = conf.get(EXTRA_PLUGINS)
    out = []
    for entry in (spec or "").split(","):
        entry = entry.strip()
        if not entry:
            continue
        mod_name, _, attr = entry.partition(":")
        mod = importlib.import_module(mod_name)
        fn = getattr(mod, attr or "init_plugin")
        out.append(fn(conf))
    return out
