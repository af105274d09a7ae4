"""One module a data kind: `make(spec, seed)` of `<kind>.py` builds the
federation a configuration's `data` group describes (harness/data.py finds it
by the group's `kind`)."""
