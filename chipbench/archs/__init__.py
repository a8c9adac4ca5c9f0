"""One module per configuration `arch`, found by name (`registry.arch`),
and `common`, the parts they share."""
