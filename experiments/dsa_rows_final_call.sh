bash experiments/dsa_rows_runs.sh step1
bash experiments/dsa_rows_runs.sh runs dsv32-dsa-decode.climb 1 change:2147485011 parent:2147485011
bash experiments/dsa_rows_runs.sh runs dsv32-dsa-decode.climb 0 parent:2147485123 change:2147485123 change:2147485237 parent:2147485237 parent:2147485349 change:2147485349
bash experiments/dsa_rows_runs.sh runs dsv3-mla-decode.climb 0 parent:2147486003 change:2147486003
bash experiments/dsa_rows_runs.sh runs trinity-attn32k.climb 0 change:2147486111 parent:2147486111
