"""Builders: one module per architecture, named by a configuration's
``builder`` key.  Each turns the configuration's sizes into the program's
own model and says how to check it against the plain reference."""
