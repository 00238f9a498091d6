"""Traffic: ``generate.py`` turns a mix file into a seeded schedule; each
``<kind>.py`` drives the program with it."""
