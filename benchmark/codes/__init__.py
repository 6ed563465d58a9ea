"""One reference file per erasure code: each module lists the
profiles it covers in ``NAMES`` (``<plugin>:<technique>``, or
``<plugin>`` alone) and gives ``layout(profile)``; see
`benchmark.reference.code_module`. A new code is a new file here."""
