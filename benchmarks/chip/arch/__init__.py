"""One module per architecture: ``arch/<model_type>.py``, found from the
configuration file's ``model_type``. Each gives

- ``program(conf) -> dict``: the registry ``ArchConfig`` attributes the
  file fixes, with their values (the run adds the two dtypes and refuses a
  registry entry that departs from them); ValueError where the file breaks
  a fact of the architecture;
- ``matmul_params(conf) -> int``: the non-embedding matmul parameters one
  token passes through (for ``mfu``, ``work/model_step.py``);
- ``check(params, w, seqs, max_len, *, control=False) -> dict``: the plain
  reference over the served requests, with ``w`` a ``discover.Widths``
  (whose ``config`` holds the file's top-level settings): ``{"gaps":
  [...]}``, and with ``control`` ``"control_gaps"``, as ``arch/qwen2.py``
  describes.

``conf`` is the configuration file's contents. A module imports nothing
of the program.
"""
