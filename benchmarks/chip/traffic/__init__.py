"""Traffic: mixes are data files (``<mix>.json``); each names its loop
kind, whose code is the module ``<loop>.py`` of this package."""
